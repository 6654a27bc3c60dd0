"""Percent of the traced window in which no operation ran on the chips
used: one minus the union of the device's operation intervals over the
window, averaged over the chips."""


def read(r):
    return r.idle_pct()
