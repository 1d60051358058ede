"""Percent of the bandwidth roofline reached by version selection.

The numerator is the least traffic the question asks for, counted from
its shapes and not from the kernel's own algorithm: for each scan, read
every cell's timestamp once (C x 4 bytes) and write one int32 count per
(query timestamp, row) (Q x N x 4 bytes). C is the cells the reference
says the store holds, N its rows, Q the distinct versions of the call.
The denominator is the device time, in the traced window, of the
modules that implement selection: each run of the scan kernel's module
(``SCAN``) with the modules that sample its running counts at the rows'
boundaries right after it (``SAMPLE``, in ``core/store.py``
``boundary_cums``).
"""
from chipbench.tracecut import runs_after
from chipbench.readers import bandwidth_share

SCAN = {"jit__batched_masked_cumsum", "jit__stacked_masked_cumsum"}
SAMPLE = ("jit__take", "jit_broadcast_in_dim", "jit__where")


def read(run):
    if run.trace is None:
        return None
    p = run.program
    nbytes = sum(4 * p["n_cells"] + 4 * q * p["n_rows"]
                 for _t, q in p["calls"])
    secs = runs_after(run.trace["module_runs"], SCAN, SAMPLE)
    return bandwidth_share(run, nbytes, secs)
