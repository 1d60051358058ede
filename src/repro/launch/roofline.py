"""Roofline-term extraction from compiled dry-run artifacts.

Per (arch x shape x mesh):
  compute   = HLO_FLOPs_per_device / peak_FLOPs      [s]
  memory    = HLO_bytes_per_device / HBM_bw          [s]
  collective= collective_bytes_per_device / link_bw  [s]

cost_analysis() of the SPMD-partitioned executable reports per-device
FLOPs/bytes; collective bytes are parsed from the partitioned HLO text with
ring-algorithm traffic factors (all-reduce 2(n-1)/n, all-gather/all-to-all
(n-1)/n on the gathered size, reduce-scatter (n-1) on the scattered size,
permute 1x). Hardware constants: the dry-run targets a v5e chip.
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Peak rates of one chip, with where they were published."""
    flops: float       # bf16 FLOP/s per chip
    hbm_bw: float      # HBM bytes/s per chip
    source: str


#: per-chip peaks keyed by ``jax.Device.device_kind``. A device that is
#: not listed (the CPU included) gets no roofline fraction.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        197e12, 819e9,
        'Google Cloud TPU docs, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM'),
}

# the dry-run compiles for a v5e (launch/dryrun.py)
PEAK_FLOPS = PEAKS["TPU v5 lite"].flops
HBM_BW = PEAKS["TPU v5 lite"].hbm_bw
LINK_BW = 50e9               # bytes/s per ICI link (1 link assumed per hop)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?P<rtype>\([^)]*\)|[a-z0-9]+\[[^\]]*\]\S*)\s+"
    r"(?P<op>all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"(?:\()")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device traffic bytes by collective kind (ring factors applied)."""
    out = {"all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0, "n_ops": 0}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op").replace("-start", "")
        nbytes = _shape_bytes(m.group("rtype"))
        gm = _GROUP_IOTA_RE.search(line)
        if gm:
            n = int(gm.group(2))
        else:
            gl = _GROUP_LIST_RE.search(line)
            n = len(gl.group(1).split(",")) if gl else 2
        n = max(n, 2)
        if op == "all-reduce":
            traffic = 2.0 * nbytes * (n - 1) / n
        elif op == "all-gather":
            traffic = nbytes * (n - 1) / n          # nbytes = gathered size
        elif op == "reduce-scatter":
            traffic = nbytes * (n - 1)              # nbytes = scattered size
        elif op == "all-to-all":
            traffic = nbytes * (n - 1) / n
        else:
            traffic = float(nbytes)
        out[op] += traffic
        out["n_ops"] += 1
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective: dict
    model_flops_global: float
    n_devices: int

    @property
    def collective_bytes_total(self) -> float:
        return sum(v for k, v in self.collective.items() if k != "n_ops")

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_total / LINK_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / compiled HLO FLOPs (remat/redundancy waste)."""
        hlo_global = self.flops_per_device * self.n_devices
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-FLOPs utilization if the dominant term were saturated:
        (model flops time) / max(term) — the score we hillclimb."""
        t_model = self.model_flops_global / (self.n_devices * PEAK_FLOPS)
        t_max = max(self.t_compute, self.t_memory, self.t_collective)
        return t_model / t_max if t_max else 0.0

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes": self.collective_bytes_total,
            "collective_detail": self.collective,
            "model_flops_global": self.model_flops_global,
            "n_devices": self.n_devices,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "useful_flops_fraction": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
        }


def kernel_roofline(flops: float, nbytes: float, wall_s: float, *,
                    device_kind: str | None = None) -> dict:
    """Single-kernel roofline terms from host-side launch accounting.

    ``flops``/``nbytes`` are the launch path's analytic estimates (see
    ``obs/kerneltel.py`` per-site models), ``wall_s`` the measured
    launch-to-host-sync wall. ``roofline_fraction`` is the fraction of
    the roofline-implied minimum time actually achieved —
    ``max(t_compute, t_memory) / wall`` against the :data:`PEAKS` entry
    of ``device_kind`` (default: the first JAX device's). For a device
    with no entry every term is None: there is no peak to divide by.
    """
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    peaks = PEAKS.get(device_kind)
    if peaks is None:
        return {"device_kind": device_kind, "t_compute_s": None,
                "t_memory_s": None, "dominant": None,
                "roofline_fraction": None}
    t_compute = flops / peaks.flops
    t_memory = nbytes / peaks.hbm_bw
    t_min = max(t_compute, t_memory)
    return {
        "device_kind": device_kind,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "dominant": "compute" if t_compute >= t_memory else "memory",
        "roofline_fraction": (t_min / wall_s) if wall_s > 0 else 0.0,
    }


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N_active*D for training, 2*N_active*D for a
    forward-only step (+ attention term for long contexts)."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1)
    mult = 6.0 if shape.mode == "train" else 2.0
    base = mult * n * tokens
    # attention FLOPs (QK^T + PV), significant at 32k
    if not cfg.rwkv:
        attn_layers = sum(1 for l in range(cfg.n_layers) if cfg.is_attn_layer(l))
        s = shape.seq_len
        if shape.mode == "decode":
            att = 2 * 2 * cfg.n_heads * cfg.hd * s  # one query over s keys
        else:
            att = 2 * 2 * cfg.n_heads * cfg.hd * s * (s + 1) / 2  # causal
        fb = 3.0 if shape.mode == "train" else 1.0
        base += fb * attn_layers * shape.global_batch * att
    return base
