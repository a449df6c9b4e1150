"""Model builder: one `Model` facade over every assigned architecture.

Design rules that keep compile cost constant in depth and memory bounded:
  * all layer stacks are `lax.scan` over stacked weights (vmapped init);
  * the cross-entropy never materializes (B, S, V) logits — it scans over
    sequence chunks with rematerialized projections;
  * attention is chunked (flash-style) for S > 1, matvec for decode;
  * caches are stacked (L, ...) arrays threaded through the scan as xs/ys.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeConfig
from repro.models import blocks as B
from repro.models import ssm as ssm_lib
from repro.models.attention import gqa_attention
from repro.models.blocks import BlockCtx, block_kind
from repro.distributed.policy import shard_logits, shard_residual
from repro.models.layers import embed, matmul, rms_norm, unembed

Array = jax.Array


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer sliding window (0 = full). Gemma3: every (r+1)-th global."""
    if cfg.local_global_ratio > 0:
        r = cfg.local_global_ratio
        w = [0 if (i + 1) % (r + 1) == 0 else cfg.sliding_window
             for i in range(cfg.num_layers)]
        return jnp.asarray(w, jnp.int32)
    return jnp.full((cfg.num_layers,), cfg.sliding_window, jnp.int32)


def hybrid_attn_layers(cfg: ModelConfig) -> tuple[jnp.ndarray, jnp.ndarray, int]:
    """(is_attn (L,), app_idx (L,), num_apps) for zamba2-style stacks.
    Computed with numpy so the pattern stays CONCRETE under jit tracing."""
    import numpy as np
    k = cfg.hybrid_attn_every
    is_attn = np.asarray([(i + 1) % k == 0 for i in range(cfg.num_layers)])
    app_idx = np.cumsum(is_attn.astype(np.int32)) - 1
    n_apps = int(is_attn.sum())
    return jnp.asarray(is_attn), jnp.asarray(app_idx), n_apps


class Model:
    """Pure-functional model: params/caches are pytrees, methods are
    trace-friendly functions of (params, batch[, cache])."""

    def __init__(self, cfg: ModelConfig, use_kernel: bool = False,
                 backend: Optional[str] = None):
        self.cfg = cfg
        self.use_kernel = use_kernel
        # routed-expert engine backend override (None = phase-driven auto;
        # see repro.core.experts.select_backend)
        self.backend = backend
        self.kind = block_kind(cfg)

    # ------------------------------------------------------------- init

    def init(self, key: Array):
        cfg = self.cfg
        dt = _dtype(cfg)
        keys = jax.random.split(key, 8)
        init_block, _ = B.BLOCKS[self.kind]
        params: dict[str, Any] = {}
        params["embed"] = (jax.random.normal(
            keys[0], (cfg.vocab_size, cfg.d_model), jnp.float32)
            * cfg.d_model ** -0.5).astype(dt)
        params["final_norm"] = jnp.zeros((cfg.d_model,), dt)
        if not cfg.tie_embeddings:
            params["lm_head"] = (jax.random.normal(
                keys[1], (cfg.d_model, cfg.vocab_size), jnp.float32)
                * cfg.d_model ** -0.5).astype(dt)

        moe_every = cfg.moe.moe_every if cfg.moe is not None else 1
        if self.kind in ("moe", "mla_moe") and moe_every > 1:
            n_per = cfg.num_layers // moe_every
            bkeys = jax.random.split(keys[2], n_per)
            params["blocks_moe"] = jax.vmap(
                lambda k: init_block(k, cfg, dt))(bkeys)
            dkeys = jax.random.split(keys[3], cfg.num_layers - n_per)
            params["blocks_dense"] = jax.vmap(
                lambda k: B.init_dense_block(k, cfg, dt))(dkeys)
        else:
            bkeys = jax.random.split(keys[2], cfg.num_layers)
            params["blocks"] = jax.vmap(
                lambda k: init_block(k, cfg, dt))(bkeys)

        if cfg.family == "hybrid":
            params["shared_attn"] = B.init_dense_block(keys[4], cfg, dt)
        if cfg.family == "audio":
            enc = cfg.encoder
            ekeys = jax.random.split(keys[5], enc.num_layers)
            params["enc_blocks"] = jax.vmap(
                lambda k: B.init_dense_block(k, cfg, dt))(ekeys)
            params["enc_pos"] = (jax.random.normal(
                keys[6], (enc.num_frames, cfg.d_model), jnp.float32)
                * 0.02).astype(dt)
            params["enc_norm"] = jnp.zeros((cfg.d_model,), dt)
        if cfg.family == "vlm" and cfg.vision and cfg.vision.d_patch:
            params["vision_proj"] = (jax.random.normal(
                keys[7], (cfg.vision.d_patch, cfg.d_model), jnp.float32)
                * cfg.vision.d_patch ** -0.5).astype(dt)
        return params

    def abstract_params(self):
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------ embed

    def _embed(self, params, batch) -> Array:
        cfg = self.cfg
        tokens = batch["tokens"] if "tokens" in batch else batch["token"]
        x = embed(tokens, params["embed"])
        if cfg.family == "vlm" and "patches" in batch:
            patches = batch["patches"].astype(x.dtype)
            if "vision_proj" in params:
                patches = matmul(patches, params["vision_proj"])
            p = patches.shape[1]
            x = jnp.concatenate([patches, x[:, p:]], axis=1)
        return shard_residual(x)

    def _encode(self, params, frames: Array) -> Array:
        """Whisper encoder over precomputed frame embeddings (stub frontend)."""
        cfg = self.cfg
        x = frames.astype(_dtype(cfg)) + params["enc_pos"][None, :frames.shape[1]]
        positions = jnp.arange(frames.shape[1])

        def body(x, p):
            ctx = BlockCtx(positions=positions, cache=None, cache_pos=None,
                           window=0, causal=False, use_rope=False,
                           use_kernel=self.use_kernel)
            x, _, _ = B.dense_block(x, p, cfg, ctx)
            return shard_residual(x), None

        x, _ = jax.lax.scan(body, x, params["enc_blocks"])
        return rms_norm(x, params["enc_norm"], cfg.norm_eps)

    # ------------------------------------------------------------ stack

    def _stack(self, params, x: Array, *, caches=None, cache_pos=None,
               enc_out=None, remat: bool = False, capture: bool = False,
               phase: str = "prefill", token_valid=None,
               block_tables=None, row_slots=None, row_k=None,
               backend=None):
        """Run the layer stack. Returns (x, new_caches, aux)."""
        cfg = self.cfg
        seq = x.shape[1]
        if cache_pos is not None:
            cp = jnp.asarray(cache_pos)
            if cp.ndim == 1:        # per-slot offsets -> (B, S) positions
                positions = cp[:, None] + jnp.arange(seq)
            else:
                positions = cp + jnp.arange(seq)
        else:
            positions = jnp.arange(seq)
        windows = layer_windows(cfg)
        base = BlockCtx(positions=positions, cache=None, cache_pos=cache_pos,
                        window=0, causal=True, use_rope=True,
                        use_kernel=self.use_kernel, capture=capture,
                        phase=phase,
                        backend=backend if backend is not None
                        else self.backend,
                        token_valid=token_valid, block_table=block_tables,
                        row_slots=row_slots, row_k=row_k)
        _, block_fn = B.BLOCKS[self.kind]
        moe_every = cfg.moe.moe_every if cfg.moe is not None else 1

        if cfg.family == "hybrid":
            return self._stack_hybrid(params, x, base, caches, remat)

        if self.kind in ("moe", "mla_moe") and moe_every > 1:
            return self._stack_interleaved(params, x, base, caches, remat,
                                           block_fn)

        if cfg.family == "audio":
            base = base._replace(cross_kv=enc_out)

        def body(x, inp):
            p, cache_sl, window = inp
            ctx = base._replace(cache=cache_sl, window=window)
            x, nc, aux = block_fn(x, p, cfg, ctx)
            return shard_residual(x), (nc, aux)

        body = _maybe_remat(body, remat)
        xs = (params["blocks"], caches, windows)
        x, (ncaches, aux) = jax.lax.scan(body, x, xs)
        return x, ncaches, aux

    def _stack_interleaved(self, params, x, base, caches, remat, block_fn):
        """llama4-style alternating dense / MoE layers: scan over periods."""
        cfg = self.cfg
        cd, cm = caches if caches is not None else (None, None)

        def body(x, inp):
            pd, pm, csd, csm = inp
            ctx = base._replace(cache=csd)
            x, ncd, aux_d = B.dense_block(x, pd, cfg, ctx)
            ctx = base._replace(cache=csm)
            x, ncm, aux = block_fn(x, pm, cfg, ctx)
            if base.capture:
                aux = {**aux, "ffn_in_dense": aux_d["ffn_in"]}
            return shard_residual(x), ((ncd, ncm), aux)

        body = _maybe_remat(body, remat)
        xs = (params["blocks_dense"], params["blocks_moe"], cd, cm)
        x, (ncaches, aux) = jax.lax.scan(body, x, xs)
        return x, ncaches, aux

    def _stack_hybrid(self, params, x, base, caches, remat):
        """zamba2: scanned Mamba2 layers + ONE shared attn block applied every
        `hybrid_attn_every` layers (its own KV cache per application)."""
        cfg = self.cfg
        is_attn, app_idx, n_apps = hybrid_attn_layers(cfg)
        mamba_caches, attn_k, attn_v = (caches if caches is not None
                                        else (None, None, None))
        shared = params["shared_attn"]

        def body(carry, inp):
            x, ak, av = carry
            p, m_cache, flag, aidx = inp
            ctx = base._replace(cache=m_cache)
            x, nmc, _ = B.mamba_block(x, p, cfg, ctx)

            def with_attn(x, ak, av):
                if ak is not None:
                    kc = jax.lax.dynamic_index_in_dim(ak, aidx, 0, False)
                    vc = jax.lax.dynamic_index_in_dim(av, aidx, 0, False)
                    cache, pos = (kc, vc), base.cache_pos
                else:
                    cache, pos = None, None
                ctx2 = base._replace(cache=cache, cache_pos=pos)
                x, nkv, _ = B.dense_block(x, shared, cfg, ctx2)
                if ak is not None:
                    ak = jax.lax.dynamic_update_index_in_dim(
                        ak, nkv[0], aidx, 0)
                    av = jax.lax.dynamic_update_index_in_dim(
                        av, nkv[1], aidx, 0)
                return x, ak, av

            x, ak, av = jax.lax.cond(
                flag, with_attn, lambda x, ak, av: (x, ak, av), x, ak, av)
            return (shard_residual(x), ak, av), nmc

        body = _maybe_remat(body, remat)
        (x, nak, nav), nmc = jax.lax.scan(
            body, (x, attn_k, attn_v),
            (params["blocks"], mamba_caches, is_attn, app_idx))
        return x, (nmc, nak, nav), {}

    # ------------------------------------------------------------ public

    def forward(self, params, batch, *, remat: bool = False) -> Array:
        """Full-sequence logits (small models/tests only)."""
        x = self._embed(params, batch)
        enc_out = None
        if self.cfg.family == "audio":
            enc_out = self._encode(params, batch["frames"])
        x, _, _ = self._stack(params, x, enc_out=enc_out, remat=remat)
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        head = params["embed"] if self.cfg.tie_embeddings else params["lm_head"]
        return unembed(x, head, self.cfg.tie_embeddings)

    def hidden_states(self, params, batch) -> Array:
        """Final-norm hidden states (no unembed) — used by profiling."""
        x = self._embed(params, batch)
        enc_out = None
        if self.cfg.family == "audio":
            enc_out = self._encode(params, batch["frames"])
        x, _, _ = self._stack(params, x, enc_out=enc_out)
        return x

    def ffn_inputs(self, params, batch):
        """Per-layer pre-FFN activations over a calibration batch — the `x`
        whose FFN hidden states CMoE profiles. Returns (L, B, S, d) (or a
        dict {"dense": ..., "moe": ...} for interleaved MoE stacks)."""
        x = self._embed(params, batch)
        enc_out = None
        if self.cfg.family == "audio":
            enc_out = self._encode(params, batch["frames"])
        _, _, aux = self._stack(params, x, enc_out=enc_out, capture=True)
        if isinstance(aux, dict) and "ffn_in_dense" in aux:
            return {"moe": aux["ffn_in"], "dense": aux["ffn_in_dense"]}
        if isinstance(aux, dict):
            return aux["ffn_in"]
        return aux

    def loss(self, params, batch, *, remat: bool = True,
             ce_chunk: int = 512):
        """Next-token CE with sequence-chunked logits (never (B,S,V))."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, {**batch, "tokens": tokens[:, :-1]})
        enc_out = None
        if cfg.family == "audio":
            enc_out = self._encode(params, batch["frames"])
        x, _, aux = self._stack(params, x, enc_out=enc_out, remat=remat)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        targets = tokens[:, 1:]
        mask = jnp.ones_like(targets, jnp.float32)
        if cfg.family == "vlm" and "patches" in batch:
            p = batch["patches"].shape[1]
            mask = mask.at[:, :p].set(0.0)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        loss = chunked_ce(x, head, cfg.tie_embeddings, targets, mask,
                          chunk=ce_chunk)
        metrics = {}
        if isinstance(aux, dict) and "load" in aux:
            metrics["moe_load"] = aux["load"]       # (L, E)
        elif isinstance(aux, tuple):
            pass
        return loss, metrics

    # ------------------------------------------------------------ caches

    def init_cache(self, batch_size: int, max_len: int, abstract=False):
        cfg = self.cfg
        dt = _dtype(cfg)
        make = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else \
            (lambda s, d: jnp.zeros(s, d))
        L = cfg.num_layers
        hd = cfg.resolved_head_dim

        def attn_cache(n_layers):
            return (make((n_layers, batch_size, max_len, cfg.num_kv_heads,
                          hd), dt),
                    make((n_layers, batch_size, max_len, cfg.num_kv_heads,
                          hd), dt))

        def mla_cache(n_layers):
            m = cfg.mla
            return (make((n_layers, batch_size, max_len, m.kv_lora_rank), dt),
                    make((n_layers, batch_size, max_len, m.qk_rope_head_dim),
                         dt))

        def mamba_cache(n_layers):
            di = ssm_lib.d_inner(cfg)
            n = cfg.ssm.state_size
            nh = ssm_lib.num_ssm_heads(cfg)
            hp = di // nh
            return (make((n_layers, batch_size, cfg.ssm.conv_width - 1,
                          di + 2 * n), dt),
                    make((n_layers, batch_size, nh, hp, n), jnp.float32))

        if cfg.family == "hybrid":
            _, _, n_apps = hybrid_attn_layers(cfg)
            k, v = attn_cache(n_apps)
            return (mamba_cache(L), k, v)
        if cfg.family == "ssm":
            return mamba_cache(L)
        if self.kind == "mla_moe":
            return mla_cache(L)
        if cfg.family == "audio":
            enc = cfg.encoder
            return {"self": attn_cache(L),
                    "cross": (make((L, batch_size, enc.num_frames,
                                    cfg.num_kv_heads, hd), dt),
                              make((L, batch_size, enc.num_frames,
                                    cfg.num_kv_heads, hd), dt))}
        moe_every = cfg.moe.moe_every if cfg.moe is not None else 1
        if self.kind == "moe" and moe_every > 1:
            n_per = L // moe_every
            return (attn_cache(L - n_per), attn_cache(n_per))
        return attn_cache(L)

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         abstract=False):
        """Paged KV pool: the per-token K/V of ``init_cache`` with the
        contiguous (B, max_len) slot-lane axes replaced by a flat pool of
        fixed-size blocks — every leaf is (L, num_blocks, block_size,
        ...). A GQA leaf folds the kv heads into the lanes, (..., KH *
        hd): the minor dim stays whole, so XLA keeps the pool row-major
        and each layer's slice reaches the paged-attention kernel with
        no relayout copy. Lanes address the pool through per-slot block
        tables (threaded to attention as ``step(block_tables=...)``); by
        the serving engine's convention physical block 0 is the trash
        block that absorbs dummy/spill writes (see
        ``repro.serving.cache.PagedKVCache``). Only the slot-addressable
        families the serving engine accepts are supported."""
        cfg = self.cfg
        dt = _dtype(cfg)
        make = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else \
            (lambda s, d: jnp.zeros(s, d))
        L = cfg.num_layers
        hd = cfg.resolved_head_dim

        def attn_pool(n_layers):
            shape = (n_layers, num_blocks, block_size, cfg.num_kv_heads * hd)
            return make(shape, dt), make(shape, dt)

        if self.kind == "mla_moe":
            m = cfg.mla
            return (make((L, num_blocks, block_size, m.kv_lora_rank), dt),
                    make((L, num_blocks, block_size, m.qk_rope_head_dim),
                         dt))
        if self.kind in ("dense", "moe"):
            moe_every = cfg.moe.moe_every if cfg.moe is not None else 1
            if self.kind == "moe" and moe_every > 1:
                n_per = L // moe_every
                return (attn_pool(L - n_per), attn_pool(n_per))
            return attn_pool(L)
        raise NotImplementedError(
            f"paged cache serves the slot-addressable KV families; "
            f"kind={self.kind!r} is not one")

    def step(self, params, tokens: Array, cache, slot_pos, *,
             phase: Optional[str] = None,
             lengths: Optional[Array] = None,
             extras: Optional[dict] = None,
             return_stats: bool = False,
             block_tables: Optional[Array] = None,
             row_slots: Optional[Array] = None,
             row_k: Optional[Array] = None,
             backend: Optional[str] = None):
        """Unified slot-aware step — the serving engine's one entry point.

        Runs `tokens` (B, S) against `cache`, writing K/V at per-slot
        offsets `slot_pos`: a (B,) int32 vector giving each batch lane its
        own write position — a freshly recycled slot prefills at 0 while
        its neighbors keep decoding at their own depths, and a CHUNKED
        prefill resumes mid-prompt at its cursor (rope positions, ragged
        attention masks, and cache writes all follow slot_pos + i, so a
        chunk attends the slot's already-filled prefix exactly as the
        whole prompt would have) — or a scalar shared by the whole batch:
        the scalar form lowers to the original chunked-flash /
        dynamic-slice path, so `prefill` and `decode_step` are thin views
        over this method with zero cost.

        `phase` ("prefill" | "decode" | "mixed", default by S) is threaded
        to the routed-expert engine so every micro-batch picks its own
        backend (ragged grouped for prefill chunks, gather for decode,
        width-thresholded for a fused "mixed" (R, 1) step — all drop-free
        under the engine's per-token capacity contract). Attention never
        reads it: the per-row fused path triggers on `row_slots` /
        per-row `block_tables`, not on phase.
        `lengths` (B,) marks each row's valid token count when prompts are
        right-padded: logits are taken at position lengths-1 and padded
        keys land beyond the valid range where masks never look (they are
        overwritten as the slot decodes forward). `extras` carries
        non-token inputs (e.g. vlm patches) through to the embedder.
        `block_tables` (B, nblk) switches the cache to the PAGED layout
        (`init_paged_cache` leaves, one layer-invariant table per lane):
        K/V writes scatter through the table and attention assembles each
        lane's logical view from the pool — same rope positions, same
        ragged masks, so a paged step computes the same function as the
        contiguous slot step.
        `row_slots` (B,) switches the CONTIGUOUS cache to the FUSED ragged
        layout (S must be 1): batch row r is an independent width-1 token
        addressed to global cache lane row_slots[r] at position
        slot_pos[r] — several rows may share a lane (a prefill chunk
        flattened into consecutive positions), and attention writes all
        rows into the shared cache before any row reads its lane's view,
        so intra-step siblings compose exactly causally. The paged layout
        needs no row_slots: per-row block tables already address the
        shared pool.
        `row_k` (B,) int32 is the per-row effective routed top-k
        (request activation TIERS): every token of row b routes through
        row_k[b] experts, with the config top_k as the static K_max — k
        is DATA, so mixed-tier rows co-batch in one compiled step. None
        (the default tier everywhere) is bitwise-identical to the
        pre-tier path. `backend` statically overrides the routed-expert
        backend for this call (the serving executor passes its
        per-row-k-aware policy choice here so the executed backend
        matches the logged one); None keeps the model-level override /
        auto selection.

        Returns (logits (B, V) at each row's last valid position,
        new_cache) — or, with ``return_stats=True``, (logits, new_cache,
        stats) where stats["dropped"] is the micro-batch's total routed
        (token, expert) pairs any bounded-buffer dispatch stage failed to
        keep, summed over layers (identically zero on the buffer-free
        engine backends — the serving executor aggregates this into
        `EngineReport` so capacity drops are surfaced, never silent).
        Audio keeps its enc-dec paths (`prefill`/`decode_step` dispatch
        there before reaching here).
        """
        cfg = self.cfg
        if cfg.family == "audio":
            raise NotImplementedError(
                "step() serves the KV-cache families; audio prefill/decode "
                "keep their enc-dec cross-attention paths")
        s = tokens.shape[1]
        if phase is None:
            phase = "decode" if s == 1 else "prefill"
        batch = {"tokens": tokens} if not extras else \
            {**extras, "tokens": tokens}
        x = self._embed(params, batch)
        token_valid = None
        if lengths is not None:
            # (B, S) mask: padding beyond each row's prompt must not
            # consume routed-expert capacity (threaded to the engine)
            token_valid = (jnp.arange(s)[None, :] <
                           jnp.asarray(lengths, jnp.int32)[:, None])
        x, ncaches, aux = self._stack(params, x, caches=cache,
                                      cache_pos=slot_pos, phase=phase,
                                      token_valid=token_valid,
                                      block_tables=block_tables,
                                      row_slots=row_slots, row_k=row_k,
                                      backend=backend)
        if lengths is None:
            xl = x[:, -1:]
        else:
            idx = (jnp.asarray(lengths, jnp.int32) - 1)[:, None, None]
            xl = jnp.take_along_axis(
                x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[2])),
                axis=1)
        xl = rms_norm(xl, params["final_norm"], cfg.norm_eps)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = unembed(xl, head, cfg.tie_embeddings)[:, 0]
        if return_stats:
            dropped = jnp.int32(0)
            if isinstance(aux, dict) and "dropped" in aux:
                dropped = jnp.sum(aux["dropped"]).astype(jnp.int32)
            return logits, ncaches, {"dropped": dropped}
        return logits, ncaches

    def prefill(self, params, batch, *, max_len: Optional[int] = None):
        """Teacher-less forward filling a fresh cache. Returns
        (last-token logits (B, V), cache). A view over `step` (scalar
        position 0 keeps the chunked-flash path) for every family but
        audio, which fills its cross-attn cache here."""
        cfg = self.cfg
        tokens = batch["tokens"]
        bsz, seq = tokens.shape[0], tokens.shape[1]
        max_len = max_len or seq
        cache = self.init_cache(bsz, max_len)
        if cfg.family == "audio":
            x = self._embed(params, batch)
            enc_out = self._encode(params, batch["frames"])
            # fill cross-attn cache
            def xkv(carry, p_block):
                return carry, B.cross_kv_project(enc_out, p_block["xattn"],
                                                 cfg)
            _, cross = jax.lax.scan(xkv, None, params["blocks"])
            x, ncaches, _ = self._stack(params, x, caches=cache["self"],
                                        cache_pos=jnp.int32(0),
                                        enc_out=enc_out)
            x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
            head = params["embed"] if cfg.tie_embeddings \
                else params["lm_head"]
            logits = unembed(x, head, cfg.tie_embeddings)[:, 0]
            return logits, {"self": ncaches, "cross": cross}
        extras = {k: v for k, v in batch.items() if k not in
                  ("tokens", "token")}
        return self.step(params, tokens, cache, jnp.int32(0),
                         phase="prefill", extras=extras or None)

    def decode_step(self, params, token: Array, cache, pos: Array):
        """One decode step. token: (B, 1) int32; pos: () or per-slot (B,)
        int32 — the index the new token is written at. A view over `step`
        for every family but audio. Returns (logits (B, V), new_cache)."""
        cfg = self.cfg
        if cfg.family == "audio":
            x = self._embed(params, {"tokens": token})
            x, ncaches, _ = self._stack_audio_decode(
                params, x, cache["self"], cache["cross"], pos)
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            head = params["embed"] if cfg.tie_embeddings \
                else params["lm_head"]
            logits = unembed(x, head, cfg.tie_embeddings)[:, 0]
            return logits, {"self": ncaches, "cross": cache["cross"]}
        return self.step(params, token, cache, pos, phase="decode")

    def _stack_audio_decode(self, params, x, caches, cross, pos):
        cfg = self.cfg
        base = BlockCtx(positions=pos + jnp.arange(1), cache=None,
                        cache_pos=pos, window=0, causal=True, use_rope=True,
                        use_kernel=self.use_kernel, phase="decode",
                        backend=self.backend)

        def body(x, inp):
            p, cache_sl, ck, cv = inp
            ctx = base._replace(cache=cache_sl, cross_kv=(ck, cv))
            x, nc, aux = B.encdec_block(x, p, cfg, ctx)
            return shard_residual(x), (nc, aux)

        x, (ncaches, _) = jax.lax.scan(
            body, x, (params["blocks"], caches, cross[0], cross[1]))
        return x, ncaches, {}

    # -------------------------------------------------------- input specs

    def input_specs(self, shape: ShapeConfig) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of this shape."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        dt = _dtype(cfg)
        sds = jax.ShapeDtypeStruct
        if shape.kind == "train":
            specs = {"tokens": sds((b, s + 1), i32)}
            if cfg.family == "audio":
                specs["frames"] = sds((b, cfg.encoder.num_frames,
                                       cfg.d_model), dt)
            if cfg.family == "vlm":
                specs["patches"] = sds((b, cfg.vision.num_patches,
                                        cfg.d_model), dt)
            return specs
        if shape.kind == "prefill":
            specs = {"tokens": sds((b, s), i32)}
            if cfg.family == "audio":
                specs["frames"] = sds((b, cfg.encoder.num_frames,
                                       cfg.d_model), dt)
            if cfg.family == "vlm":
                specs["patches"] = sds((b, cfg.vision.num_patches,
                                        cfg.d_model), dt)
            return specs
        # decode: one new token against a seq_len cache
        return {"token": sds((b, 1), i32),
                "cache": self.init_cache(b, s, abstract=True),
                "pos": sds((), i32)}


def _maybe_remat(body, remat):
    """remat: False | True (save layer inputs only) | "dots" (save matmul
    outputs — recompute only the cheap elementwise chains)."""
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_saveable)
    if remat:
        return jax.checkpoint(body)
    return body


def chunked_ce(x: Array, head: Array, tied: bool, targets: Array,
               mask: Array, chunk: int = 512) -> Array:
    """CE over sequence chunks; logits for each chunk are rematerialized in
    the backward pass (jax.checkpoint) so (B, S, V) never exists."""
    b, s, d = x.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = (s + pad) // chunk
    xc = x.reshape(b, nc, chunk, d).swapaxes(0, 1)
    tc = targets.reshape(b, nc, chunk).swapaxes(0, 1)
    mc = mask.reshape(b, nc, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_loss(carry, inp):
        xb, tb, mb = inp
        logits = shard_logits(unembed(xb, head, tied).astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mb
        return (carry[0] + nll.sum(), carry[1] + mb.sum()), None

    (tot, cnt), _ = jax.lax.scan(chunk_loss, (jnp.float32(0), jnp.float32(0)),
                                 (xc, tc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def build_model(cfg: ModelConfig, use_kernel: bool = False,
                backend: Optional[str] = None) -> Model:
    return Model(cfg, use_kernel=use_kernel, backend=backend)


def count_params(cfg: ModelConfig) -> int:
    import math
    m = Model(cfg)
    tree = m.abstract_params()
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))
