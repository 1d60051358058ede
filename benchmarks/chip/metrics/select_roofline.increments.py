"""Percent of the bandwidth roofline reached by the selection behind
``get_increments``: the count and the device time of
``select_roofline.versions``, with Q the distinct window ends of each
call."""
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
