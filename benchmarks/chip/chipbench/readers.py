"""Arithmetic the metric files share: percentiles of host-clock
latencies, the table of peaks, and shares of a roofline."""
from __future__ import annotations

import json
import os

import numpy as np

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def latencies_ms(run) -> list[float]:
    """Submit-to-answer milliseconds of every request sent in the window,
    those answered after its close included: a tail is the tail of all
    requests, and the one in flight at the close is the likeliest long."""
    return [(r.t_done - r.t_submit) * 1e3 for r in run.answered()]


def percentile(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device missing from the
    table is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}")
    return table[device_kind]


def bandwidth_share(run, nbytes: float, seconds: float) -> float | None:
    """Percent of the bandwidth roofline: the least time to move
    ``nbytes`` at peak HBM bandwidth over ``seconds`` of device time in
    the traced window. None when no such time was traced or no bytes were
    asked for: a share is never reported as 0 for want of a reading."""
    if run.trace is None or nbytes <= 0 or seconds <= 0:
        return None
    return 100.0 * nbytes / peaks(run.device_kind)["hbm_bytes_per_s"] / seconds


def idle_share(run) -> float | None:
    """Percent of the traced window in which no operation ran on the
    device."""
    if run.trace is None or run.trace["busy_s"] is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
