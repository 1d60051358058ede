"""From a ``jax.profiler`` trace (``.xplane.pb``) to device numbers.

The window is the host annotation ``WINDOW`` that the harness wraps
around the traced part of a run. On each device plane (``/device:...``)
the operations are the events of the ``XLA Ops`` line; busy time is the
union of their intervals inside the window, averaged over the devices
that ran anything, and each operation's device time is summed under its
name, prefixed with the jit module (``XLA Modules`` line) that ran it.
Each module run is also kept in device order, so that a reader can take
a kernel together with the operations that consume its output. An idle
gap is a stretch of the window in which no operation ran on
the device; it is named after the host event that overlaps it most.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host events that say nothing of what the host was doing
_HOST_NOISE = ("ThreadpoolListener", "$")
UNTRACED = "(no host event)"


def find_trace(directory: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_trace(path: str) -> dict:
    """``{"window_s", "busy_s", "devices", "op_s": {name: s},
    "gaps": [[host event, s], ...]}`` of the trace at ``path``;
    ``busy_s`` is None when no device plane ran an operation."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    host_events = []
    device_ops: dict[str, list] = {}
    device_modules: dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    into = device_ops if line.name == OPS_LINE else \
                        device_modules
                    into.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.duration_ns > 0 and not e.name.startswith(
                            _HOST_NOISE):
                        host_events.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} host annotation")
    w0, w1 = window
    op_s: dict[str, float] = defaultdict(float)
    module_runs: list[list] = []
    busy = []
    gaps_by_host: dict[str, float] = defaultdict(float)
    for plane, ops in device_ops.items():
        clipped = [(max(s, w0), min(e, w1), n) for n, s, e in ops
                   if e > w0 and s < w1]
        if not clipped:
            continue
        mods = sorted((max(s, w0), min(e, w1), module_name(n))
                      for n, s, e in device_modules.get(plane, ())
                      if e > w0 and s < w1)
        module_runs += [[n, (e - s) * 1e-9] for s, e, n in mods]
        for s, e, n in clipped:
            op_s[f"{_enclosing(mods, s)}: {n}"] += (e - s) * 1e-9
        merged = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                if g1 > g0]
        for name, secs in _name_gaps(host_events, gaps):
            gaps_by_host[name] += secs
    n_dev = len(busy)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n_dev if n_dev else None,
        "devices": n_dev,
        "op_s": dict(op_s),
        "module_runs": module_runs,
        "gaps": sorted(([k, v / max(n_dev, 1)]
                        for k, v in gaps_by_host.items()),
                       key=lambda kv: -kv[1]),
    }


def module_name(event_name: str) -> str:
    """``jit__take(1223...)`` -> ``jit__take``: the module's name without
    the fingerprint of its program."""
    return event_name.split("(", 1)[0]


def _enclosing(mods, t) -> str:
    """The module whose run covers device time ``t`` (``mods`` sorted)."""
    i = bisect.bisect_right(mods, (t, float("inf"))) - 1
    if i >= 0 and mods[i][0] <= t < mods[i][1]:
        return mods[i][2]
    return "(no module)"


def runs_after(module_runs, first: set, then: tuple) -> float:
    """Device seconds of every run of a module in ``first`` together with
    the runs that directly follow it on the device and match ``then`` in
    order (each entry a module name, skipped where the next run is not
    it): a kernel with the operations that consume its output."""
    total, i = 0.0, 0
    while i < len(module_runs):
        name, secs = module_runs[i]
        i += 1
        if name not in first:
            continue
        total += secs
        for want in then:
            if i < len(module_runs) and module_runs[i][0] == want:
                total += module_runs[i][1]
                i += 1
    return total


def _name_gaps(host_events, gaps):
    """Yield (host event, seconds) for each idle gap, naming it after the
    host event that overlaps it most; one sweep over both sorted lists."""
    events = sorted(host_events, key=lambda ev: ev[1])
    i, active = 0, []
    for g0, g1 in gaps:
        while i < len(events) and events[i][1] < g1:
            active.append(events[i])
            i += 1
        active = [ev for ev in active if ev[2] > g0]
        best, best_ov = UNTRACED, 0
        for name, s, e in active:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = name, ov
        yield best, (g1 - g0) * 1e-9

