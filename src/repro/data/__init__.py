"""Data pipelines: synthetic tabular suites (OpenML stand-ins) and file
readers/writers."""
