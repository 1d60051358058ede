"""Idle gaps named by the program's own profiler leaves.

The program's stages hold ``jax.profiler.TraceAnnotation``s named
``gestore.<stage>.<leaf>`` while they run, on the thread that drives the
device, and no leaf encloses another (``repro.obs.trace.StageTimer``).
The trace reduction names each idle gap after the host event that
overlaps it most (``tracecut._name_gaps``), so a gap inside a leaf is
named after the leaf. The shares here read ``run.trace["gaps"]``.
"""
from __future__ import annotations

PREFIX = "gestore."


def _gaps(run):
    """The traced run's named idle gaps; None without a trace, when no
    device ran an operation, or when no gap is named by a leaf (a
    program that writes none)."""
    t = run.trace
    if t is None or t["busy_s"] is None:
        return None
    gaps = t["gaps"]
    if not any(name.startswith(PREFIX) for name, _s in gaps):
        return None
    return gaps


def leaf_idle_share(run, leaf: str) -> float | None:
    """Percent of the traced window in which the device was idle in gaps
    named after the leaf ``gestore.<leaf>``."""
    gaps = _gaps(run)
    if gaps is None:
        return None
    secs = sum(s for name, s in gaps if name == PREFIX + leaf)
    return 100.0 * secs / run.trace["window_s"]


def untraced_idle_share(run) -> float | None:
    """Percent of the traced window in which the device was idle in gaps
    that no leaf names: JAX's own host events, or none."""
    gaps = _gaps(run)
    if gaps is None:
        return None
    secs = sum(s for name, s in gaps if not name.startswith(PREFIX))
    return 100.0 * secs / run.trace["window_s"]
