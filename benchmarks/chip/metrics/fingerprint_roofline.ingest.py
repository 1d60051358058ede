"""Percent of the bandwidth roofline reached by change detection.

The numerator counts the question from its shapes: every field of every
ingested row read once at its stored width, and one 8-byte digest per
row and field written. The rows are the entries applied in the traced
window. The denominator is the device time of the fingerprint kernel's
module, ``FINGERPRINT`` below, in that window.
"""
import numpy as np

from chipbench.readers import bandwidth_share

FINGERPRINT = "jit_fingerprint"


def read(run):
    if run.trace is None:
        return None
    d = run.cell.config["deployment"]
    row = sum(w * np.dtype(dt).itemsize + 8 for w, dt in (
        (d["seq_width"], "int8"), (1, "int32"), (d["annot_width"], "int8"),
        (1, "int32")))
    secs = sum(s for n, s in run.trace["module_runs"] if n == FINGERPRINT)
    return bandwidth_share(run, run.program["entries_routed"] * row, secs)
