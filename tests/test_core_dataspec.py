"""Dataspec inference, overrides, encodings, safety errors (paper §2.1/2.2)."""
import numpy as np
import pytest

from repro.core import Task, YdfError
from repro.core.dataspec import (
    Semantic,
    check_classification_label,
    dataset_from_raw,
    encode_dataset,
    infer_dataspec,
)


def _data():
    return {
        "age": np.array([25, 38, None, 52, 17], dtype=object),
        "color": np.array(["red", "blue", "red", None, "green"], dtype=object),
        "flag": np.array([True, False, True, True, False], dtype=object),
        "mixed": np.array(["2", "x", "3", "2", "x"], dtype=object),
    }


def test_semantic_inference():
    spec = infer_dataspec(_data())
    assert spec["age"].semantic == Semantic.NUMERICAL
    assert spec["color"].semantic == Semantic.CATEGORICAL
    assert spec["flag"].semantic == Semantic.BOOLEAN
    assert spec["mixed"].semantic == Semantic.CATEGORICAL  # non-numeric present
    assert spec["age"].n_missing == 1
    assert spec.n_rows == 5


def test_boolean_columns_bin_as_ordered_two_bin_features():
    """A boolean (here MSLR's 0/1 boolean-model feature) bins as an ordered
    feature split at 0.5, not as a category, so a table of numbers and
    booleans stays on the device grower's fused kernel; a missing value
    takes the majority."""
    from repro.core.binning import bin_features
    data = {"flag": np.array([1.0, 0.0, 1.0, 1.0, 0.0, None], dtype=object),
            "x": np.array([0.5, 2.0, 3.5, 1.0, 7.0, 2.5], dtype=object)}
    ds = dataset_from_raw(data)
    assert ds.spec["flag"].semantic == Semantic.BOOLEAN
    b = bin_features(ds, ["flag", "x"])
    assert not b.is_cat.any()
    assert list(b.codes[:, 0]) == [1, 0, 1, 1, 0, 1]
    assert b.n_bins[0] == 2 and b.threshold_value(0, 1) == 0.5


def test_user_override_wins_and_is_flagged():
    spec = infer_dataspec(_data(), semantics={"age": "CATEGORICAL"})
    assert spec["age"].semantic == Semantic.CATEGORICAL
    assert spec["age"].manually_defined
    assert "manually-defined" in spec.report()


def test_vocab_is_frequency_ordered_with_ood():
    spec = infer_dataspec(_data())
    assert spec["color"].vocab[0] == "<OOD>"
    assert spec["color"].vocab[1] == "red"  # most frequent


def test_encoding_missing_and_ood():
    spec = infer_dataspec(_data())
    ds = encode_dataset(_data(), spec)
    assert np.isnan(ds.numerical["age"][2])
    assert ds.categorical["color"][3] == -1  # missing
    new = dict(_data())
    new["color"] = np.array(["purple"] * 5, dtype=object)  # unseen
    ds2 = encode_dataset(new, spec)
    assert (ds2.categorical["color"] == 0).all()  # OOD bucket


def test_numerical_override_with_strings_raises_helpfully():
    with pytest.raises(YdfError, match="CATEGORICAL"):
        infer_dataspec(_data(), semantics={"mixed": "NUMERICAL"})


def test_classification_label_looks_like_regression():
    """The paper's §2.2 safety check, with actionable message."""
    col = infer_dataspec({"revenue": np.arange(5000, dtype=float)})["revenue"]
    with pytest.raises(YdfError, match="task=REGRESSION"):
        check_classification_label(col, Task.CLASSIFICATION)


def test_mismatched_column_lengths():
    with pytest.raises(YdfError, match="same length"):
        infer_dataspec({"a": np.arange(3), "b": np.arange(4)})


def test_report_contains_stats():
    rep = infer_dataspec(_data()).report()
    assert "NUMERICAL" in rep and "CATEGORICAL" in rep
    assert "vocab-size" in rep and "mean" in rep


def test_single_class_label_error_mentions_solutions():
    from repro.core import GradientBoostedTreesLearner
    data = {"x": np.arange(50, dtype=float).astype(object),
            "y": np.array(["only"] * 50, dtype=object)}
    with pytest.raises(YdfError, match="classe"):
        GradientBoostedTreesLearner(label="y", num_trees=2).train(data)


def test_unknown_hyperparameter_error():
    from repro.core import GradientBoostedTreesLearner
    with pytest.raises(YdfError, match="Known hyper-parameters"):
        GradientBoostedTreesLearner(label="y", num_treez=5)


def test_csv_roundtrip(tmp_path):
    from repro.data.io import read_dataset, write_dataset
    data = _data()
    path = f"csv:{tmp_path}/d.csv"
    write_dataset(data, path)
    back = read_dataset(path)
    assert set(back) == set(data)
    assert back["age"][2] is None
    assert list(back["color"][:3]) == ["red", "blue", "red"]
    with pytest.raises(YdfError, match="format-prefixed"):
        read_dataset(str(tmp_path / "d.csv"))
