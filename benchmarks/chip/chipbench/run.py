"""One run of one cell: set-up, the measured window, the readings, the
comparison with the plain reference, and the result line.

The order is fixed: build the deployment from the seed and warm every
shape the traffic uses (``setup_s``); open the window (with ``trace``,
under the profiler); close it, wait for the answers still due; read the
device's peak memory; take the readings the metric files need; free the
program's state; then compare the kept answers with the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time

from . import tracecut
from .spec import Cell

#: the numbers that decide ``correct``, each with its limit: rows of the
#: kept answers that differ from the reference, requests that failed or
#: never answered, and answers compared (at least one)
LIMITS = {"wrong_rows": ("max", 0), "failed": ("max", 0),
          "compared": ("min", 1)}


#: a device operation's name in the breakdown: its module and the head of
#: its HLO text
NAME_CHARS = 160


def own_environment(root: str) -> None:
    """The run's environment, set before JAX is imported: every
    ``GESTORE_*`` variable dropped, so that the program's built-in
    settings decide what runs, and JAX's persistent compile cache at the
    fixed ``<checkout>/.jax_cache``, every program in it."""
    for k in [k for k in os.environ if k.startswith("GESTORE_")]:
        del os.environ[k]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def profile_options():
    """The profiler's settings for a traced window: device operations and
    the host's own events, without the Python tracer, which records every
    Python call and slows the host-bound paths several times over."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(chips: int):
    """The TPU devices the run may use; raises ``NoChip`` otherwise: a
    measurement never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devices[0].platform} "
                     f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX has {len(devices)}")
    return devices


class Compiles:
    """Counts, through ``jax.monitoring``, the executables a run had to
    get: each compiled or loaded from the persistent cache (``hits``),
    with its name, so that one got inside the window can be named."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.n = 0
        self.hits = 0
        self.secs = 0.0
        self.names: list[str] = []

    def install(self) -> "Compiles":
        import jax

        def on_duration(event, duration, **kw):
            if event == self.BACKEND:
                self.n += 1
                self.secs += duration
                self.names.append(str(kw.get("fun_name")))

        def on_event(event, **_kw):
            if event == self.HIT:
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self


@dataclasses.dataclass
class RunRecord:
    """What the metric files read (``read(run)``)."""
    cell: Cell
    seconds: float
    setup_s: float
    t_open: float
    t_close: float
    records: list
    at_close: dict
    program: dict                  # readings taken from the program
    device_kind: str
    trace: dict | None = None      # tracecut.reduce_trace, with --trace 1

    def answered(self):
        """Requests sent in the window and answered, in it or in the
        drain after its close."""
        return [r for r in self.records if r.error is None
                and r.t_done is not None]

    def done_in_window(self):
        return [r for r in self.answered() if r.t_done <= self.t_close]


def _written_bytes() -> int | None:
    """Bytes this process has sent to storage so far (Linux only)."""
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("write_bytes:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return None


def _memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, work_root: str, devices,
             control: bool = False, compiles: Compiles | None = None,
             log=sys.stderr) -> dict:
    """Run ``cell`` once; returns the result dict (the last stdout line).
    ``control`` also returns the control's verdict under ``"control"``,
    from the same limits (the benchmark's own runs never ask for it). Several
    runs in one process share one installed ``compiles``."""
    import jax

    compiles = compiles or Compiles().install()
    os.makedirs(work_root, exist_ok=True)
    work = tempfile.mkdtemp(prefix="run-", dir=work_root)
    try:
        t_built = time.perf_counter()
        dep = cell.deployment(cell.config, seed, work)
        op = cell.op(dep, cell.mix, seed)
        t_warm, n_built = time.perf_counter(), compiles.n
        op.warm()
        print(f"setup: start {t_built - t_start:.2f} s, deployment "
              f"{t_warm - t_built:.2f} s ({n_built} executables), warm "
              f"{time.perf_counter() - t_warm:.2f} s "
              f"({compiles.n - n_built})", file=log, flush=True)
        trace_dir = os.path.join(work, "trace")
        if trace:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profile_options())
        n_compiles = compiles.n
        setup_s = time.perf_counter() - t_start
        with jax.profiler.TraceAnnotation(tracecut.WINDOW):
            t_open = time.perf_counter()
            t_close = t_open + seconds
            op.start(t_close)
            time.sleep(max(0.0, t_close - time.perf_counter()))
            at_close = op.at_close()
            op.join()
            op.stop()
            t_drained = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        in_window = compiles.names[n_compiles:]
        peak = _memory_peak(devices)
        program = op.collect()
        records = list(op.records)
        op.close()
        dep.close()
        del dep
        gc.collect()
        reduced = None
        if trace:
            path = tracecut.find_trace(trace_dir)
            if path is None:
                raise RuntimeError("the profiler wrote no trace")
            reduced = tracecut.reduce_trace(path)
        run = RunRecord(cell=cell, seconds=seconds, setup_s=setup_s,
                        t_open=t_open, t_close=t_close, records=records,
                        at_close=at_close, program=program,
                        device_kind=devices[0].device_kind, trace=reduced)
        t_check = time.perf_counter()
        wrong, compared = op.check()
        t_check = time.perf_counter() - t_check
        failed = sum(1 for r in records
                     if r.error is not None or r.t_done is None)
        checks = {"wrong_rows": wrong, "failed": failed,
                  "compared": compared}
        result = _result(cell, run, trace, devices, peak, checks,
                         len(records), failed)
        print(f"setup_s={setup_s!r} window_compiles={len(in_window)} "
              f"{sorted(set(in_window))} executables={compiles.n} "
              f"cache_hits={compiles.hits} compile_s_total={compiles.secs!r} "
              f"requests={len(records)} "
              f"answered_in_window={len(run.done_in_window())} "
              f"written_bytes={_written_bytes()} check_s={t_check:.2f} "
              f"drain_s={t_drained - t_close:.2f} at_close={at_close} program="
              f"{ {k: v for k, v in program.items() if _scalar(v)} }",
              file=log, flush=True)
        if control:
            c_wrong, c_compared = op.check(control=True)
            c_ok, c_checks = verdict({"wrong_rows": c_wrong, "failed": 0,
                                      "compared": c_compared})
            result["control"] = {"correct": c_ok, "checks": c_checks}
        for name, c in result["checks"].items():
            print(f"check {name}={c['value']} limit "
                  f"{'<=' if c.get('max') is not None else '>='}"
                  f"{c.get('max', c.get('min'))}", file=log, flush=True)
        return result
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _result(cell, run, trace, devices, peak, checks, attempted, failed):
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.reader.read(run)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": None, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        top = sorted(run.trace["op_s"].items(), key=lambda kv: -kv[1])
        out["breakdown"] = {"device_ops": [[k[:NAME_CHARS], v]
                                           for k, v in top[:10]],
                            "idle_gaps": run.trace["gaps"][:10]}
    out["correct"], out["checks"] = verdict(checks)
    return out


def verdict(checks: dict) -> tuple[bool, dict]:
    """(correct, each number beside its limit): ``correct`` when every
    number in ``checks`` keeps its limit in ``LIMITS``."""
    compared = {}
    ok = True
    for name, value in checks.items():
        kind, limit = LIMITS[name]
        compared[name] = {"value": value, kind: limit}
        ok &= value <= limit if kind == "max" else value >= limit
    return bool(ok), compared


def _scalar(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def dumps(result: dict) -> str:
    return json.dumps(result, separators=(",", ":"))
