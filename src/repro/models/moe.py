"""Mixture-of-Experts with static-shape capacity dispatch (EP-shardable).

Dispatch computes each (token, choice)'s position within its expert's
capacity buffer with two stable argsorts (rank within expert group); tokens
past capacity are dropped (Switch/GLaM semantics, capacity_factor controls
the drop rate). The (E*C, d) dispatch buffer keeps every shape static,
scatters/gathers are XLA ops, and the expert dimension carries the
``expert`` logical axis so EP falls out of the sharding rules (kimi: 384
experts / 16-way model axis = 24 experts per device; grok's 8 experts don't
divide the axis so the rules fall back to expert-FFN tensor parallelism).
Sharding constraints pin token-major tensors to DP and the capacity buffer
to EP; see EXPERIMENTS.md §Perf for the before/after roofline terms.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import ParamSpec


def moe_spec(cfg) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    s = {
        "router": ParamSpec((d, e), ("embed", "expert_in"), init="small"),
        "wi": ParamSpec((e, d, f), ("expert", "embed", "expert_mlp")),
        "wo": ParamSpec((e, f, d), ("expert", "expert_mlp", "embed")),
    }
    if cfg.mlp_gated:
        s["wg"] = ParamSpec((e, d, f), ("expert", "embed", "expert_mlp"))
    return s


def apply_moe(p: dict, x: jax.Array, cfg, *,
              no_drop: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    no_drop=True sizes capacity at the worst case (T*k) so no token is ever
    dropped — required on serving decode steps where T is tiny."""
    from repro.sharding.rules import constrain  # lazy: avoids import cycle
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xf = x.reshape(t, d)
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    gate, idx = jax.lax.top_k(probs, k)                          # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean((jax.nn.one_hot(idx[:, 0], e)), axis=0)
    aux = e * jnp.sum(me * ce)

    cap = t * k if no_drop else int(max(1, round(t * k / e * cfg.capacity_factor)))
    # position-within-expert via two sorts (NOT a (T*k, E) one-hot cumsum:
    # that lowers to a reduce-window XLA costs as ~O(T*k*E) extra flops and
    # dominated the kimi-k2 compute term ~200x — see EXPERIMENTS.md §Perf).
    # Stable sort by expert id groups assignments; rank - group_start is the
    # arrival-order position, identical semantics to the cumsum scheme.
    eid = idx.reshape(-1)                                        # (T*k,)
    order = jnp.argsort(eid, stable=True)
    inv = jnp.argsort(order, stable=True)                        # rank of i
    counts = jnp.bincount(eid, length=e)                         # (E,)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = inv - starts[eid]                                      # (T*k,)
    keep = pos < cap
    slot = jnp.where(keep, eid * cap + pos, e * cap)             # overflow slot

    # dispatch buffer: (E*cap + pad, d); row e*cap is the drop/overflow bin,
    # padding keeps dim0 shardable. Sharding constraints pin the dataflow:
    # token-major tensors ride DP, the capacity buffer rides EP (model axis)
    # so dispatch/combine lower to all-to-alls instead of replication (the
    # grok/kimi collective-term pathology, EXPERIMENTS.md §Perf iter 2).
    xrep = jnp.repeat(xf, k, axis=0)                             # (T*k, d)
    xrep = constrain(xrep, ("batch", "act_embed"))
    pad = (-(e * cap + 1)) % 256 + 1
    buf = jnp.zeros((e * cap + pad, d), x.dtype).at[slot].set(xrep)
    hbuf = constrain(buf[: e * cap].reshape(e, cap, d),
                     ("expert", "exp_cap", "act_embed"))

    h = jnp.einsum("ecd,edf->ecf", hbuf, p["wi"].astype(x.dtype))
    if cfg.mlp_gated:
        g = jnp.einsum("ecd,edf->ecf", hbuf, p["wg"].astype(x.dtype))
        h = act(g) * h
    else:
        h = act(h)
    ybuf = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(x.dtype))
    ybuf = constrain(ybuf, ("expert", "exp_cap", "act_embed"))
    ybuf = jnp.concatenate([ybuf.reshape(e * cap, d),
                            jnp.zeros((pad, d), x.dtype)], axis=0)

    y = ybuf[slot] * (gate.reshape(-1) * keep)[:, None].astype(x.dtype)
    y = constrain(y, ("batch", "act_embed"))
    y = y.reshape(t, k, d).sum(axis=1)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# shard_map expert parallelism: expert-LOCAL dispatch
# ---------------------------------------------------------------------------
# GSPMD cannot shard a data-dependent scatter: the pjit dispatch above gets
# "involuntarily rematerialized" into per-device full all-gathers of the
# (T*k, d) token buffer (~240 GB/layer/device for kimi-k2 — EXPERIMENTS.md
# SPerf iter 2/3). Under shard_map the scatter is provably local: tokens
# stay on their DP shard, every model-axis peer routes the SAME replicated
# activations to the experts (EP mode) or expert-FFN slice (TP mode) it
# owns, and the only combine collective is one psum over `model` of the
# (T_local, d) outputs — the information-theoretic floor for capacity-based
# MoE without token re-layout.

def _moe_local(xf, router, wi, wg, wo, cfg, *, e_lo: int, e_local: int,
               cap: int, axis: str | None, act):
    """Per-device body. xf: (T_loc, d) replicated over `model`; weights are
    this peer's expert slice. Computes this peer's contribution; caller
    psums over `model`."""
    t, d = xf.shape
    k, e = cfg.top_k, cfg.n_experts
    logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e), axis=0)
    aux = e * jnp.sum(me * ce)

    eid = idx.reshape(-1)
    mine = (eid >= e_lo) & (eid < e_lo + e_local)
    eid_m = jnp.where(mine, eid - e_lo, e_local)       # sentinel bucket
    order = jnp.argsort(eid_m, stable=True)
    inv = jnp.argsort(order, stable=True)
    counts = jnp.bincount(eid_m, length=e_local + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = inv - starts[eid_m]
    keep = mine & (pos < cap)
    slot = jnp.where(keep, eid_m * cap + pos, e_local * cap)

    xrep = jnp.repeat(xf, k, axis=0)
    buf = jnp.zeros((e_local * cap + 1, d), xf.dtype).at[slot].set(xrep)
    hbuf = buf[: e_local * cap].reshape(e_local, cap, d)
    h = jnp.einsum("ecd,edf->ecf", hbuf, wi.astype(xf.dtype))
    if wg is not None:
        h = act(jnp.einsum("ecd,edf->ecf", hbuf, wg.astype(xf.dtype))) * h
    else:
        h = act(h)
    ybuf = jnp.einsum("ecf,efd->ecd", h, wo.astype(xf.dtype))
    ybuf = jnp.concatenate([ybuf.reshape(e_local * cap, d),
                            jnp.zeros((1, d), xf.dtype)], axis=0)
    y = ybuf[slot] * (gate.reshape(-1) * keep)[:, None].astype(xf.dtype)
    y = y.reshape(t, k, d).sum(axis=1)
    if axis is not None:
        y = jax.lax.psum(y, axis)
        aux = jax.lax.pmean(aux, axis)
    return y, aux


def apply_moe_sharded(p: dict, x: jax.Array, cfg, *, no_drop: bool = False):
    """EP/TP MoE via shard_map when a mesh context is active; falls back to
    apply_moe otherwise. EP mode: each `model` peer owns E/n experts.
    TP mode (E not divisible, e.g. grok's 8 on a 16-way axis): each peer
    owns a d_ff_expert/n slice of EVERY expert; the same combine psum also
    completes the partial contraction."""
    import math
    from jax.sharding import PartitionSpec as P
    from repro.sharding.rules import current_mesh
    mesh = current_mesh()
    if mesh is None or "model" not in (mesh.axis_names or ()):
        return apply_moe(p, x, cfg, no_drop=no_drop)
    n_model = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = math.prod(mesh.shape[a] for a in dp_axes)
    b, s, d = x.shape
    e, k, f = cfg.n_experts, cfg.top_k, cfg.d_ff_expert
    ep_mode = e % n_model == 0 and e >= n_model
    tp_mode = (not ep_mode) and f % n_model == 0
    if b % dp != 0 or not (ep_mode or tp_mode):
        return apply_moe(p, x, cfg, no_drop=no_drop)

    t_loc = (b // dp) * s
    cap = (t_loc * k if no_drop else
           int(max(1, round(t_loc * k / e * cfg.capacity_factor))))
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    bspec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    e_local = e // n_model if ep_mode else e
    w_spec = P("model", None, None) if ep_mode else P(None, None, "model")
    wo_spec = P("model", None, None) if ep_mode else P(None, "model", None)

    def body(x_loc, router, wi, wg, wo):
        xf = x_loc.reshape(-1, d)
        e_lo = (jax.lax.axis_index("model") * e_local) if ep_mode else 0
        y, aux = _moe_local(xf, router, wi,
                            (wg if cfg.mlp_gated else None), wo, cfg,
                            e_lo=e_lo, e_local=e_local, cap=cap,
                            axis="model", act=act)
        for a in dp_axes:
            aux = jax.lax.pmean(aux, a)
        return y.reshape(x_loc.shape), aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None), w_spec,
                  (w_spec if cfg.mlp_gated else P()), wo_spec),
        out_specs=(P(bspec, None, None), P()))
    # NOTE (§Perf iter 4, REFUTED): casting weights to bf16 before the
    # shard_map boundary was hypothesized to halve gather traffic; measured
    # +6.5% collective instead — the dominant term is the f32 gradient
    # all-reduce over `data`, which the pre-cast cannot touch.
    return fn(x, p["router"], p["wi"],
              (p["wg"] if cfg.mlp_gated else jnp.zeros((), x.dtype)), p["wo"])
