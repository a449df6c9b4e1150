"""Chip smoke: serve the CMoE-converted qwen1.5-0.5b on one TPU chip.

Drives the repo's main path once, in this one process, through the same
entry point as ``python -m repro.launch.serve``:

1. refuses to run (exit 1, no result line) unless JAX's default backend
   is a TPU — it never falls back to the CPU or to Pallas interpret mode;
2. turns on the persistent compilation cache
   (``repro.launch.compile_cache``);
3. compares each hot-path Pallas kernel with its XLA path at the served
   shapes in bf16, through the seams the model calls
   (``paged_decode_attention``, ``routed_experts``), within the bf16
   tolerance of the repo's kernel parity tests;
4. serves 8 requests with ``--continuous --paged`` (the overlapped fused
   step, chunked prefill, kernels on) at qwen1.5-0.5b's published widths
   in bf16, from seeded weights converted S3A3E8 (3 shared + 3 of 5
   routed experts: 75% of the FFN active);
5. checks that every request finished, that no routed pair was dropped,
   that the block-pool audit passed, that the kernels were on, and that
   every token is in the vocabulary.

It prints the device, the kernel errors, the engine summary and the
compile count, and as its last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Timings in the engine summary are a cold run: compiles are inside them.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.experts import routed_experts  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.attention import paged_decode_attention  # noqa: E402

ARCH, CMOE = "qwen1.5-0.5b", "S3A3E8"
SLOTS, REQUESTS, PROMPT, GEN, BUDGET, BLOCK = 4, 8, 128, 16, 64, 16
# the repo's bf16 kernel-parity tolerance (tests/test_paged_kernels.py)
ATOL = RTOL = 5e-2


class CompileLog:
    """Counts XLA compiles (and persistent-cache hits) via jax.monitoring."""

    def __init__(self):
        self.n = self.hits = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def require(ok, what: str):
    """A failed check ends the run (explicit, so `python -O` keeps it)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def _check(name, got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    require(got.shape == want.shape, f"{name}: {got.shape} != {want.shape}")
    require(np.isfinite(got).all() and np.isfinite(want).all(),
            f"{name}: non-finite output")
    err = float(np.abs(got - want).max())
    print(f"[kernels] {name}: max |kernel - xla| = {err:.3e} "
          f"(|xla| max {float(np.abs(want).max()):.3e}; "
          f"tolerance atol {ATOL} + rtol {RTOL})")
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=RTOL,
                               err_msg=name)


def compare_kernels(cfg, seed: int = 0):
    """Each kernel against its XLA path at the served shapes: the widest
    fused step (4 decode lanes + a 64-token chunk, 16-token blocks over
    144-token lanes), and the widest step the policy sends to gather."""
    cm = serve.parse_sxayez(CMOE)
    kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    d, m, e, k = cfg.d_model, cfg.d_ff // cm.num_experts, cm.num_routed, \
        cm.top_k
    rows = SLOTS + BUDGET
    nblk = (PROMPT + GEN) // BLOCK
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    bf = jnp.bfloat16

    q = jax.random.normal(ks[0], (rows, 1, cfg.num_heads, hd), bf)
    pools = [jax.random.normal(kk, (SLOTS * nblk + 1, BLOCK, kh, hd), bf)
             for kk in ks[1:3]]
    table = jax.random.randint(ks[3], (rows, nblk), 1, SLOTS * nblk + 1)
    pos = jax.random.randint(ks[4], (rows,), 0, nblk * BLOCK)

    def attn(use_kernel):
        return jax.jit(lambda q, kp, vp, t, p: paged_decode_attention(
            q, kp, vp, table=t, pos=p, use_kernel=use_kernel))(
                q, *pools, table, pos)
    _check("paged_attn_decode vs paged_ragged_attention", attn(True),
           attn(False))

    w = {"wg": jax.random.normal(ks[5], (e, d, m), bf) * d ** -0.5,
         "wu": jax.random.normal(ks[6], (e, d, m), bf) * d ** -0.5,
         "wd": jax.random.normal(ks[7], (e, m, d), bf) * m ** -0.5}

    def experts(t, backend, use_kernel):
        xf = jax.random.normal(ks[8], (t, d), bf)
        scores = jax.random.normal(ks[9], (t, e))
        gates, idx = jax.lax.top_k(jax.nn.softmax(scores, -1), k)
        return jax.jit(lambda xf, w, g, i: routed_experts(
            xf, w, g.astype(bf), i, cfg, backend=backend,
            use_kernel=use_kernel)[0])(xf, w, gates, idx)
    _check("moe_gather vs XLA gather", experts(8, "gather", True),
           experts(8, "gather", False))
    _check("moe_gmm_ragged vs segment_ffn_xla",
           experts(rows, "grouped_pallas", True),
           experts(rows, "grouped_xla", False))


def main() -> int:
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (default backend "
              f"{jax.default_backend()!r}); refusing to run elsewhere",
              file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    print(f"[chip] compile cache {enable_compile_cache()}")
    compiles = CompileLog()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[chip] {device}")
    cfg = get_config(ARCH)
    compare_kernels(cfg)

    model, report = serve.run([
        "--arch", ARCH, "--cmoe", CMOE, "--continuous", "--paged",
        "--batch", str(SLOTS), "--requests", str(REQUESTS),
        "--prompt-len", str(PROMPT), "--gen", str(GEN),
        "--max-prefill-tokens", str(BUDGET), "--block-size", str(BLOCK),
        "--seed", "0"])
    require(model.use_kernel, "the served model ran without its kernels")
    require(model.cfg.dtype == "bfloat16", f"served in {model.cfg.dtype}")
    require(len(report.requests) == REQUESTS, "requests went missing")
    require(all(r.done for r in report.requests), "unfinished requests")
    require(report.dropped_pairs == 0,
            f"{report.dropped_pairs} routed pairs dropped")
    require(report.pool_audit.get("ok"),
            f"pool audit failed: {report.pool_audit}")
    toks = [t for r in report.requests for t in r.generated]
    require(all(r.generated for r in report.requests),
            "a request generated nothing")
    require(all(0 <= t < cfg.vocab_size for t in toks),
            "a token outside the vocabulary")
    print(f"[chip] {len(toks)} tokens served; {compiles.n} compiles "
          f"({compiles.hits} from the persistent cache) took "
          f"{compiles.secs:.1f}s; wall {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
