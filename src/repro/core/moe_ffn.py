"""The CMoE FFN — the converted layer's runtime (paper Eq. 4).

F_MoE(x) = E_shared(x) + Σ_i g_i · E_i^routed(x)

Routed-expert execution delegates to the unified engine
(`repro.core.experts`): ragged segment dispatch (segment-blocked XLA
GEMMs or the Pallas ``moe_gmm_ragged`` kernel) for prefill-shaped calls,
the buffer-free ``gather`` path for decode, and the dense-mask ``exact``
oracle for tests (the all-active exactness invariant) and small models.
Every path is drop-free under the engine's per-token capacity contract;
the ``dropped`` aux count each forward reports is therefore zero here and
exists as the uniform surfacing seam for the bounded-buffer stages.

Param schema per layer (stacked over L inside the block scan):
  cmoe = {
    "shared": {wg,wu,wd} or {wi,wd},
    "routed": {wg,wu,wd} each (N_r, d, m) / (N_r, m, d), or {wi,wd},
    "router": {wg_r,wu_r} each (d, N_r), or {wi_r},
    "u": (N_r,) learnable scaling, "bias": (N_r,) balance bias,
  }
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.experts import dropped_pairs, routed_experts
from repro.core.router import cmoe_gate, expert_load, router_scores
from repro.models.layers import matmul, swish

Array = jax.Array


def _shared_ffn(xf: Array, p: dict, activation: str) -> Array:
    if activation in ("swiglu", "geglu"):
        g = matmul(xf, p["wg"]).astype(jnp.float32)
        u = matmul(xf, p["wu"]).astype(jnp.float32)
        act = (lambda v: v * jax.nn.sigmoid(v)) if activation == "swiglu" \
            else jax.nn.gelu
        h = (act(g) * u).astype(xf.dtype)
    else:
        h = jax.nn.gelu(matmul(xf, p["wi"]).astype(jnp.float32)).astype(
            xf.dtype)
    return matmul(h, p["wd"])


def cmoe_ffn(x: Array, p: dict, cfg, *, use_kernel: bool = False,
             capacity_factor: float = 1.25,
             backend: str | None = None, phase: str = "prefill",
             valid: Array | None = None,
             k_row: Array | None = None):
    """x: (B, S, d) or (T, d). Returns (out, aux{load, router_probs_mean}).

    valid: optional (T, 1) bool — False rows (right-padded serving
    prompts) contribute nothing: they neither occupy grouped-backend
    expert capacity nor count toward the load stats.
    k_row: optional (T,) int32 per-token effective k in [1, cm.top_k]
    (request activation tiers — cm.top_k is only the static K_max);
    assignments past each token's k are invalidated by the gate exactly
    like padding, so every backend runs unchanged.
    """
    cm = cfg.cmoe
    squeeze = x.ndim == 2
    if squeeze:
        xf = x
    else:
        b, s, d = x.shape
        xf = x.reshape(b * s, d)
    n_r = cm.num_routed

    scores = router_scores(xf, p["router"], cfg.activation)
    gates, idx, probs = cmoe_gate(
        scores, cm.top_k,
        u=p.get("u") if cm.learnable_scaling else None,
        bias=p.get("bias"), k_row=k_row)

    out, keep = routed_experts(xf, p["routed"], gates, idx, cfg,
                               backend=backend, phase=phase,
                               capacity_factor=capacity_factor,
                               use_kernel=use_kernel, valid=valid)

    out = out + _shared_ffn(xf, p["shared"], cfg.activation)
    aux = {"load": expert_load(idx, keep, n_r),
           "router_probs_mean": probs.mean(0),
           "dropped": dropped_pairs(keep, valid, idx.shape)}
    if not squeeze:
        out = out.reshape(b, s, d)
    return out, aux


# ------------------------------------------------- data-local dispatch

def cmoe_ffn_local(x: Array, p: dict, cfg, mesh, *,
                   capacity_factor: float = 1.25,
                   use_kernel: bool = False,
                   backend: str | None = None,
                   phase: str = "prefill",
                   valid: Array | None = None,
                   k_row: Array | None = None):
    """Beyond-paper optimization (§Perf): shard_map DATA-LOCAL dispatch.

    The naive GSPMD lowering of the token->expert scatter materializes the
    global (E, C, d) buffer via zero-init + ALL-REDUCE (measured 1.3 TB of
    collective bytes per device on granite prefill_32k). Here tokens never
    leave their data shard:

      * expert weights are TP-sharded on the EXPERT WIDTH m (N_r is small
        and indivisible, so EP-over-experts cannot use a 16-wide axis);
      * each device all-gathers its data-shard's sequence slice (SP), runs
        a purely LOCAL engine dispatch (grouped for prefill, gather for
        decode), computes every expert's m-slice, and reduce-scatters the
        partial outputs back to the SP layout;
      * per-layer collective bytes drop from O(E·C·d) all-reduce to
        1.5x the dense FFN's own TP traffic (gather x + scatter y).

    x: (B, S, d). Requires B % dp == 0 (caller falls back otherwise).
    k_row: optional (B, S) int32 per-token effective k — sharded like
    `valid` and threaded to the gate inside each shard's local dispatch.
    """
    from repro.distributed.policy import _dp  # local import, no cycle
    cm = cfg.cmoe
    n_r = cm.num_routed
    dp = _dp(mesh)
    msize = mesh.shape["model"] if "model" in mesh.axis_names else 1
    b, s, d = x.shape
    seq_sharded = s % msize == 0 and msize > 1 and s > 1

    x_spec = P(dp, "model" if seq_sharded else None, None)
    v_spec = P(dp, "model" if seq_sharded else None)
    if valid is None:
        valid = jnp.ones((b, s), bool)
    has_k = k_row is not None
    if k_row is None:
        k_row = jnp.full((b, s), cm.top_k, jnp.int32)
    else:
        k_row = jnp.broadcast_to(jnp.asarray(k_row, jnp.int32), (b, s))
    routed_specs = {k: P(None, "data", "model") if k != "wd"
                    else P(None, "model", "data")
                    for k in p["routed"]}
    shared_specs = {k: P("data", "model") if k != "wd"
                    else P("model", "data") for k in p["shared"]}
    router_specs = {k: P("data", None) for k in p["router"]}
    p_specs = {"shared": shared_specs, "routed": routed_specs,
               "router": router_specs, "u": P(None), "bias": P(None)}

    def local_ffn(x_loc, p_loc, v_loc, k_loc):
        # ZeRO-style param regather (FSDP over data)
        routed = {k: jax.lax.all_gather(v, "data", axis=1, tiled=True)
                  if k != "wd" else
                  jax.lax.all_gather(v, "data", axis=2, tiled=True)
                  for k, v in p_loc["routed"].items()}
        shared = {k: jax.lax.all_gather(v, "data", axis=0, tiled=True)
                  if k != "wd" else
                  jax.lax.all_gather(v, "data", axis=1, tiled=True)
                  for k, v in p_loc["shared"].items()}
        router = {k: jax.lax.all_gather(v, "data", axis=0, tiled=True)
                  for k, v in p_loc["router"].items()}
        if seq_sharded:
            xg = jax.lax.all_gather(x_loc, "model", axis=1, tiled=True)
            vg = jax.lax.all_gather(v_loc, "model", axis=1, tiled=True)
            kg = jax.lax.all_gather(k_loc, "model", axis=1, tiled=True)
        else:
            xg, vg, kg = x_loc, v_loc, k_loc
        bl, sl, _ = xg.shape
        xf = xg.reshape(bl * sl, d)
        vf = vg.reshape(bl * sl, 1)

        scores = router_scores(xf, router, cfg.activation)
        gates, idx, probs = cmoe_gate(
            scores, cm.top_k,
            u=p_loc.get("u") if cm.learnable_scaling else None,
            bias=p_loc.get("bias"),
            k_row=kg.reshape(bl * sl) if has_k else None)
        y, keep = routed_experts(xf, routed, gates, idx, cfg,
                                 backend=backend, phase=phase,
                                 capacity_factor=capacity_factor,
                                 use_kernel=use_kernel,
                                 valid=vf)  # local!
        y = y + _shared_ffn(xf, shared, cfg.activation)    # partial (m-slice)
        y = y.reshape(bl, sl, d)
        if seq_sharded:
            y = jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                     tiled=True)
        else:
            y = jax.lax.psum(y, "model")
        load = expert_load(idx, keep, n_r)
        load = jax.lax.pmean(load, "data")
        # drop counts SUM over data shards (distinct tokens per shard);
        # model-axis devices saw the same all-gathered tokens, so the
        # count is already replicated there
        dropped = jax.lax.psum(dropped_pairs(keep, vf, idx.shape), "data")
        if dp is not None and "pod" in mesh.axis_names:
            load = jax.lax.pmean(load, "pod")
            dropped = jax.lax.psum(dropped, "pod")
        pm = jax.lax.pmean(probs.mean(0), "data")
        return y, load, pm, dropped

    # replication is not checked: the body mixes per-shard and
    # psum-replicated outputs
    out_specs = (x_spec, P(None), P(None), P())
    y, load, pm, dropped = jax.shard_map(
        local_ffn, mesh=mesh,
        in_specs=(x_spec, p_specs, v_spec, v_spec), out_specs=out_specs,
        check_vma=False)(
            x, {k: p[k] for k in
                ("shared", "routed", "router", "u", "bias")
                if k in p}, valid, k_row)
    return y, {"load": load, "router_probs_mean": pm, "dropped": dropped}
