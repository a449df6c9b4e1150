"""Pretrained-MoE FFN blocks (llama4 / deepseek-v2) on top of the unified
routed-expert engine (`repro.core.experts`).

This module owns the pretrained-MoE *routing* (top-k softmax router,
balance bias, shared experts) and the two-stage all-to-all EP layout;
expert dispatch and compute live in the engine. The capacity machinery
(`expert_capacity` / `assign_positions` / `dispatch` / `combine` /
`DispatchInfo`) is re-exported from the engine for backward compatibility.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Re-exports: the dispatch machinery moved to the engine; downstream code
# (and tests) keep importing it from here.
from repro.core.experts import (DispatchInfo, assign_positions,  # noqa: F401
                                combine, dispatch, dropped_pairs,
                                expert_capacity, grouped_expert_ffn,
                                round_up, routed_experts)
from repro.models.layers import matmul, swish

Array = jax.Array


def expert_ffn(xbuf: Array, wg: Array, wu: Array, wd: Array,
               activation: str, use_kernel: bool = False) -> Array:
    """Batched expert FFN: (E, C, d) with per-expert weights (E, d, m).
    Thin glu-schema wrapper over the engine's `grouped_expert_ffn`."""
    return grouped_expert_ffn(xbuf, {"wg": wg, "wu": wu, "wd": wd},
                              activation, use_kernel=use_kernel)


def moe_gate(xf: Array, p: dict, moe):
    """Top-k softmax router with optional aux-loss-free balance bias.
    Returns (gates (T,k), idx (T,k), probs (T,E))."""
    scores = matmul(xf, p["router"]).astype(jnp.float32)     # (T, E)
    probs = jax.nn.softmax(scores, axis=-1)
    sel = probs
    if moe.balance_bias and "balance_bias" in p:
        sel = probs + p["balance_bias"][None, :]
    gates, idx = jax.lax.top_k(sel, moe.top_k)
    gates = jnp.take_along_axis(probs, idx, axis=1)          # true probs
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx, probs


def moe_ffn(x: Array, p: dict, cfg, *, use_kernel: bool = False,
            backend: str | None = None, phase: str = "prefill",
            valid: Array | None = None):
    """Pretrained-MoE FFN block (top-k softmax router + shared experts).

    x: (B, S, d). valid: optional (B*S, 1) bool — False rows (padded
    serving prompts) take no expert capacity and no load share.
    Returns (out, aux) with aux = dict(load=per-expert counts
    fraction, router_probs_mean=mean prob per expert) for balancing metrics.
    """
    moe = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    t = b * s

    gates, idx, probs = moe_gate(xf, p, moe)
    out, keep = routed_experts(
        xf, {"wg": p["wg"], "wu": p["wu"], "wd": p["wd"]}, gates, idx, cfg,
        backend=backend, phase=phase,
        capacity_factor=moe.capacity_factor, use_kernel=use_kernel,
        valid=valid)

    if moe.num_shared > 0:
        g = matmul(xf, p["shared_wg"])
        u = matmul(xf, p["shared_wu"])
        act = swish if cfg.activation == "swiglu" else jax.nn.gelu
        h = (act(g.astype(jnp.float32)) *
             u.astype(jnp.float32)).astype(x.dtype)
        out = out + matmul(h, p["shared_wd"])

    load = jnp.zeros((moe.num_experts,), jnp.float32).at[idx.reshape(-1)].add(
        keep.reshape(-1).astype(jnp.float32)) / (t * moe.top_k)
    aux = {"load": load, "router_probs_mean": probs.mean(0),
           "dropped": dropped_pairs(keep, valid, idx.shape)}
    return out.reshape(b, s, d), aux


def moe_ffn_local(x: Array, p: dict, cfg, mesh, *,
                  use_kernel: bool = False, backend: str | None = None,
                  phase: str = "prefill", valid: Array | None = None):
    """Beyond-paper optimization (§Perf): two-stage shard_map EP dispatch
    for the ROUTED experts (shared experts stay on the dense GSPMD path).

    The GSPMD lowering of the global token->expert scatter costs an
    all-reduce of the full (E, C, d) buffer per layer (dominant collective
    term on deepseek-v2 train_4k). Production layout instead:

      * tokens stay sharded over (dp x model-as-sequence): each device
        routes ONLY its own sequence slice;
      * stage 1: bin by destination model-shard (e_loc = E/msize experts
        per shard) and move via ALL-TO-ALL (+int payload: local expert id);
      * stage 2: local capacity dispatch to the shard's experts via the
        engine's grouped backend, all-to-all back, gate-weighted combine.

    Per-layer collective bytes: 2 x C_send x d all-to-all instead of the
    (E, C_global, d) all-reduce. Requires B %% dp == 0 and S %% msize == 0.
    x: (B, S, d). Returns (routed_out (B, S, d), aux).
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed.policy import _dp
    moe = cfg.moe
    e, k = moe.num_experts, moe.top_k
    dp = _dp(mesh)
    msize = mesh.shape["model"] if "model" in mesh.axis_names else 1
    assert e % msize == 0, (e, msize)
    e_loc = e // msize
    b, s, d = x.shape
    seq_sharded = s % msize == 0 and msize > 1 and s > 1
    x_spec = P(dp, "model" if seq_sharded else None, None)
    v_spec = P(dp, "model" if seq_sharded else None)
    if valid is None:
        valid = jnp.ones((b, s), bool)
    else:
        valid = valid.reshape(b, s)
    p_specs = {"router": P("data", None),
               "balance_bias": P(None),
               "wg": P("model", "data", None),
               "wu": P("model", "data", None),
               "wd": P("model", None, "data")}
    p_in = {kk: p[kk] for kk in p_specs}

    def local_moe(x_loc, pl, v_loc):
        ag = jax.lax.all_gather
        wg = ag(pl["wg"], "data", axis=1, tiled=True)      # (E_loc, d, m)
        wu = ag(pl["wu"], "data", axis=1, tiled=True)
        wd = ag(pl["wd"], "data", axis=2, tiled=True)      # (E_loc, m, d)
        router = ag(pl["router"], "data", axis=0, tiled=True)
        bl, sl, _ = x_loc.shape
        xf = x_loc.reshape(bl * sl, d)
        vf = v_loc.reshape(bl * sl, 1)
        t_loc = xf.shape[0]

        gates, idx, probs = moe_gate(
            xf, {"router": router, "balance_bias": pl["balance_bias"]}, moe)

        # ---- stage 1: all-to-all to expert-owning shards ----
        # padded tokens are re-aimed at the out-of-range shard id before
        # binning: they occupy no send-capacity slot, ship nowhere, and
        # real tokens' bin positions don't depend on padding content
        dest = jnp.where(vf, idx // e_loc, msize)          # (T_loc, k)
        cap_s = expert_capacity(t_loc, msize, k, moe.capacity_factor)
        # bounded send buffer -> per-token contract: overflow evicts the
        # lowest-gated assignments (deterministic token-id tiebreak), and
        # the shard's drop count is surfaced through aux, never silent
        pos_s, keep_s = assign_positions(dest, msize, cap_s, priority=gates)
        keep_s = keep_s & vf
        info_s = DispatchInfo(dest, pos_s, keep_s,
                              jnp.ones_like(gates).astype(xf.dtype))
        send = dispatch(xf, info_s, msize, cap_s)          # (msize, C_s, d)
        eloc_id = (idx % e_loc).astype(jnp.int32)
        flat_d = jnp.where(keep_s.reshape(-1), dest.reshape(-1), 0)
        flat_p = jnp.where(keep_s.reshape(-1), pos_s.reshape(-1), 0)
        pay = jnp.zeros((msize, cap_s), jnp.int32).at[flat_d, flat_p].max(
            jnp.where(keep_s.reshape(-1), eloc_id.reshape(-1) + 1, 0))
        recv = jax.lax.all_to_all(send, "model", 0, 0)     # (msize, C_s, d)
        pay_r = jax.lax.all_to_all(pay, "model", 0, 0)

        # ---- stage 2: local dispatch to this shard's experts ----
        xr = recv.reshape(msize * cap_s, d)
        er = pay_r.reshape(-1) - 1                         # -1 = empty slot
        occ = er >= 0
        er = jnp.maximum(er, 0)
        # decode must stay drop-free (gather); prefill keeps the grouped
        # local dispatch the EP layout was built around
        yr, _ = routed_experts(
            xr, {"wg": wg, "wu": wu, "wd": wd},
            jnp.ones((msize * cap_s, 1), xr.dtype), er[:, None], cfg,
            backend=backend or
            ("gather" if phase == "decode" else
             "grouped_pallas" if use_kernel else "grouped_xla"),
            capacity_factor=moe.capacity_factor, use_kernel=use_kernel,
            valid=occ[:, None])
        yr = yr.reshape(msize, cap_s, d)
        yback = jax.lax.all_to_all(yr, "model", 0, 0)      # home shards
        out = combine(yback,
                      DispatchInfo(dest, pos_s, keep_s,
                                   gates.astype(xf.dtype)))
        load = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(
            keep_s.reshape(-1).astype(jnp.float32))
        load = jax.lax.psum(load, "model")
        # each shard routed its OWN sequence slice: drops sum over the
        # model axis and every data axis
        dropped = jax.lax.psum(dropped_pairs(keep_s, vf, idx.shape),
                               "model")
        if dp is not None:
            axes = dp if isinstance(dp, tuple) else (dp,)
            for ax in axes:
                load = jax.lax.psum(load, ax)
                dropped = jax.lax.psum(dropped, ax)
        load = load / jnp.maximum(load.sum(), 1.0)
        pm = jax.lax.pmean(probs.mean(0), "data")
        return out.reshape(bl, sl, d), load, pm, dropped

    # replication is not checked: the body mixes per-shard and
    # psum-replicated outputs
    y, load, pm, dropped = jax.shard_map(
        local_moe, mesh=mesh, in_specs=(x_spec, p_specs, v_spec),
        out_specs=(x_spec, P(None), P(None), P()),
        check_vma=False)(x, p_in, valid)
    return y, {"load": load, "router_probs_mean": pm, "dropped": dropped}


def init_moe_ffn(key, cfg, dtype):
    moe = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 7)

    def lecun(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) *
                (1.0 / fan_in) ** 0.5).astype(dtype)

    p = {
        "router": lecun(ks[0], (d, moe.num_experts), d),
        "wg": lecun(ks[1], (moe.num_experts, d, moe.d_expert), d),
        "wu": lecun(ks[2], (moe.num_experts, d, moe.d_expert), d),
        "wd": lecun(ks[3], (moe.num_experts, moe.d_expert, d), moe.d_expert),
        "balance_bias": jnp.zeros((moe.num_experts,), jnp.float32),
    }
    if moe.num_shared > 0:
        p["shared_wg"] = lecun(ks[4], (d, moe.d_shared), d)
        p["shared_wu"] = lecun(ks[5], (d, moe.d_shared), d)
        p["shared_wd"] = lecun(ks[6], (moe.d_shared, d), moe.d_shared)
    return p
