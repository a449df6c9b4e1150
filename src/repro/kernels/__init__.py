# Pallas TPU kernel inventory. `ops` holds the jit'd public wrappers,
# `ref` the pure-jnp oracles the tests compare against. All kernels are
# inference-only (no custom VJP); training paths stay on XLA. Opt-in is
# via `ops.on_tpu()` / ModelCtx.use_kernel — off-TPU every kernel runs
# in Pallas interpret mode (bit-accurate, for correctness gates only).
#
#   swiglu.py          swiglu_ffn: fused gate*sigmoid(gate)*up -> down
#                      FFN, tiled over (tokens, d_ff); no prefetch.
#   moe_gmm.py         moe_gmm: dense per-expert grouped GEMM over the
#                      capacity buffer (E, C, d). moe_gmm_ragged: ragged
#                      segment GEMM — per-block expert OWNER ids ride
#                      scalar prefetch so each grid step DMAs exactly one
#                      expert's weight slab; rows are block-aligned by
#                      ragged_block_c() (128 on TPU, 16 in interpret —
#                      callers must pad totals to that multiple).
#   moe_gather.py      moe_gather: token-choice decode MoE. Flat expert
#                      ids (T*k,) ride scalar prefetch; grid step (i, j)
#                      DMAs only token i//k's assignment-i weight tiles
#                      (k live slabs per token) instead of XLA's
#                      materialized (T*k, d, m) gather copies. Fused
#                      gate/up/act/down per tile; combine stays in XLA.
#   paged_attention.py paged_attn_decode: GQA decode attention over the
#                      lane-dense paged KV pool (nblocks, bs, KH * hd).
#                      Per-slot block tables + positions + window ride
#                      scalar prefetch; grid (B, nblk) walks each slot's
#                      LIVE physical blocks (all KV heads per DMA, read in
#                      128-lane slices of heads) via the table index_map,
#                      masking by logical length, with
#                      online-softmax m/l/acc scratch carried across the
#                      sequential innermost dim. mla_paged_decode: same
#                      walk over the latent (cc, cp) pools, scoring
#                      absorbed queries and accumulating in latent space.
#   flash_attention.py flash_attention: causal prefill attention, online
#                      softmax over k/v blocks; no prefetch.
#   flash_decode.py    flash_decode: contiguous-cache decode attention,
#                      length-masked; superseded by paged_attn_decode for
#                      the paged engine but kept for contiguous lanes.
#   router_score.py    router_score: fused analytical router scoring
#                      act(x Wg^R) * (x Wu^R) — both skinny matmuls plus
#                      the gated activation in one pass over x.
#   ssd_scan.py        ssd_scan: Mamba2 SSD chunked state scan.
from repro.kernels import ops, ref  # noqa: F401
