"""One run of one serving cell: set-up, the timed window, the check.

Set-up (``setup_s``, from process start to the first released request):
JAX start, the dense weights made on the device from the seed, their
conversion by ``repro.core.convert.convert_dense_model`` (timed apart as
``convert_s``), the engine, and one call of the fused paged step at every
width the cell's traffic can dispatch. Then the stream: warm-up arrivals,
the measured window of ``seconds``, and arrivals on until every request
due in the window has finished. Then ``memory_peak_bytes`` is read, the
program's state is freed, and the check runs against the plain reference:
the conversion's partition against the reference's own activation
profile, and a sample of the finished requests token by token.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import spec, traffic as traffic_mod
from harness.compile_log import CompileLog
from harness.release import TracePlan, WallClockEngine, WindowDone
from repro.config import CMoEConfig, ModelConfig
from repro.core.convert import convert_dense_model
from repro.models import build_model
from repro.serving.cache import PagedKVCache
from repro.serving.request import Request

ROW_GRANULE = 4       # the engine pads a fused step to a multiple of this


def model_config(config: dict) -> ModelConfig:
    """The program's ModelConfig for a dense GQA/SwiGLU configuration
    given by its published keys."""
    if config["hidden_act"] != "silu":
        raise ValueError(f"unsupported hidden_act {config['hidden_act']!r}")
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    window = config.get("sliding_window") if config.get(
        "use_sliding_window", True) else None
    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=int(config["num_hidden_layers"]), d_model=d,
        num_heads=h, num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or d // h),
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]), activation="swiglu",
        qkv_bias=bool(config["attention_bias"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        sliding_window=int(window or 0), dtype=config["torch_dtype"])


@dataclasses.dataclass
class Run:
    """What a run leaves for the metric readers and the check."""
    cell: spec.Cell
    seed: int
    seconds: float
    setup_s: float
    convert_s: float
    engine: WallClockEngine
    compiles_in_window: int
    compiles_setup: int
    compile_hits: int
    device: dict
    memory_peak_bytes: int
    parts: list                    # the conversion's partition, per layer
    config: dict                   # the configuration as it ran
    use_kernel: bool = False       # the model ran its Pallas kernels
    trace: Optional[dict] = None   # harness.trace.reduce(...) output
    peaks: Optional[dict] = None


def fused_widths(slots: int, budget: int) -> list[int]:
    """Every row count a fused step of this engine can take: decode rows
    (at most one per lane) plus chunk rows (at most the budget), padded
    to the row granule."""
    top = -(-(slots + budget) // ROW_GRANULE) * ROW_GRANULE
    return list(range(ROW_GRANULE, top + 1, ROW_GRANULE))


def warm_up(engine: WallClockEngine, params, widths: list[int]) -> None:
    """Compile (or load from the persistent cache) and run the fused
    paged step once at each width, writing only to the trash block."""
    kv = PagedKVCache(engine.model, engine.max_slots, engine.max_len,
                      block_size=engine.block_size)
    slot_tokens = jnp.zeros((engine.max_slots,), jnp.int32)
    for r in widths:
        z = np.zeros(r, np.int32)
        f = np.zeros(r, bool)
        out = engine.executor.step_fused_paged(
            params, kv.cache, jnp.asarray(z), jnp.asarray(f), slot_tokens,
            jnp.asarray(z), jnp.asarray(np.zeros(
                (r, kv.blocks_per_slot), np.int32)), jnp.asarray(z),
            jnp.asarray(z), jnp.asarray(z), jnp.asarray(f))
        jax.block_until_ready(out[:3])
        del out
    del kv
    gc.collect()


@dataclasses.dataclass
class Served:
    """A converted model behind a warmed engine, ready for windows."""
    engine: WallClockEngine
    config: dict
    parts: list                    # the conversion's partition, per layer
    convert_s: float
    compiles: CompileLog


def convert(config: dict, seed: int, use_kernel: Optional[bool] = None):
    """(model, params, partition per layer, seconds): the dense weights
    made from the seed, converted by the program on the calibration batch
    drawn from the seed. ``use_kernel`` None = the program's own choice
    (on iff on a TPU)."""
    ref = spec.reference_module(config)
    cfg = model_config(config)
    if use_kernel is None:
        from repro.kernels import ops as kops
        use_kernel = kops.on_tpu()
    model = build_model(cfg, use_kernel=use_kernel)
    dense = ref.make_params(config, seed)
    jax.block_until_ready(dense)
    c = config["cmoe"]
    cm = CMoEConfig(num_experts=int(c["num_experts"]),
                    num_shared=int(c["num_shared"]), top_k=int(c["top_k"]),
                    k_activation=int(c["k_activation"]))
    calib = {"tokens": jnp.asarray(ref.calib_tokens(config, seed),
                                   jnp.int32)}
    t = time.perf_counter()
    model, params, conv = convert_dense_model(model, dense, calib, cm)
    jax.block_until_ready(params)
    convert_s = time.perf_counter() - t
    parts = [{"shared_idx": p.shared_idx, "routed_idx": p.routed_idx,
              "rep_idx": p.rep_idx} for p in conv.parts]
    del dense, conv
    gc.collect()
    return model, params, parts, convert_s


def set_up(cell: spec.Cell, seed: int, *, use_kernel: Optional[bool] = None,
           config_overrides: Optional[dict] = None,
           compiles: Optional[CompileLog] = None) -> Served:
    """Weights from the seed, their conversion, the engine, the warm-up.
    ``config_overrides`` replace configuration keys (CPU tests only)."""
    compiles = compiles or CompileLog()
    config = dict(cell.config, **(config_overrides or {}))
    model, params, parts, convert_s = convert(config, seed, use_kernel)

    sv = config["serving"]
    slots, budget = int(sv["slots"]), int(sv["prefill_budget"])
    engine = WallClockEngine(model, params, max_slots=slots,
                             max_len=traffic_mod.max_len(cell.traffic),
                             max_prefill_tokens=budget,
                             temperature=0.0, seed=0, paged=True,
                             block_size=int(sv["block_size"]),
                             prefix_reuse=False, overlap=True)
    warm_up(engine, params, fused_widths(slots, budget))
    return Served(engine=engine, config=config, parts=parts,
                  convert_s=convert_s, compiles=compiles)


def serve(sv: Served, cell: spec.Cell, seed: int, seconds: float, *,
          t_process: float, trace_plan: Optional[TracePlan] = None) -> Run:
    """One stream of ``cell``'s traffic (generated from ``seed``) through
    the set-up engine, until the window's requests have finished;
    ``memory_peak_bytes`` read after it."""
    engine = sv.engine
    planned = traffic_mod.generate(cell.traffic, seconds, seed,
                                   engine.model.cfg.vocab_size)
    reqs = [Request(rid=p.rid, prompt=p.prompt, max_new=p.max_new)
            for p in planned]
    engine.prime(reqs, due_s=[p.due_s for p in planned],
                 measured=[p.measured for p in planned], seconds=seconds,
                 drain_limit_s=float(cell.traffic["drain_limit_s"]),
                 trace=trace_plan, compiles=sv.compiles)
    try:
        engine.run(reqs)
    except WindowDone:
        pass
    engine.finish_trace()

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    run = Run(cell=cell, seed=seed, seconds=seconds,
              setup_s=engine.t_start - t_process, convert_s=sv.convert_s,
              engine=engine,
              compiles_in_window=engine.compiles_window[1] -
              engine.compiles_window[0],
              compiles_setup=engine.compiles_window[0],
              compile_hits=sv.compiles.hits,
              device={"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())},
              memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)),
              parts=sv.parts, config=sv.config,
              use_kernel=engine.model.use_kernel)
    return run


def free(sv: Served) -> None:
    """Drop the program's device state, so the reference runs alone."""
    sv.engine.kv = None
    sv.engine.params = None
    sv.engine.executor = None
    sv.engine.model = None
    gc.collect()


def serve_window(cell: spec.Cell, seed: int, seconds: float, *,
                 t_process: float, trace_plan: Optional[TracePlan] = None,
                 use_kernel: Optional[bool] = None,
                 config_overrides: Optional[dict] = None,
                 compiles: Optional[CompileLog] = None) -> Run:
    """Set up, serve one window, free the program's device state."""
    sv = set_up(cell, seed, use_kernel=use_kernel,
                config_overrides=config_overrides, compiles=compiles)
    run = serve(sv, cell, seed, seconds, t_process=t_process,
                trace_plan=trace_plan)
    free(sv)
    return run


def check_sample(run: Run, k: int) -> list:
    """The requests the check compares: the longest finished one and
    k - 1 others drawn from the seed."""
    done = [r for r in run.engine.released if r.done]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: (r.prompt_len + len(r.generated),
                                       -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(run.seed), 2])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def check(run: Run, *, control: bool = False) -> dict:
    """The partition's readings (``reference.partition_readings``) and
    the widest logit gap of the sampled served tokens against the float32
    reference (and, with ``control``, of the fp8 control's tokens)."""
    config = run.config
    ref = spec.reference_module(config)
    chk = config["check"]
    sample = check_sample(run, int(chk["sample_requests"]))
    dense = ref.make_params(config, run.seed)
    profile = ref.activation_profile(dense, config,
                                     ref.calib_tokens(config, run.seed))
    out = {"partition": ref.partition_readings(profile, run.parts, config)}
    del profile
    pad_to = traffic_mod.max_len(run.cell.traffic)
    out.update(requests=len(sample), tokens=0, served=0.0)
    if control:
        out["control"] = 0.0
    with jax.default_matmul_precision("highest"):
        for r in sample:
            g = ref.served_gaps(dense, run.parts, config, r.prompt,
                                r.generated, pad_to=pad_to, control=control)
            for key, val in g.items():
                out[key] = max(out[key], val)
            out["tokens"] += len(r.generated)
    del dense
    gc.collect()
    return out


def trace_dir(root: Path, cell: str) -> str:
    d = root / ".bench_trace" / cell
    if d.exists():
        shutil.rmtree(d)
    d.mkdir(parents=True)
    return str(d)


def env_flags() -> None:
    """Process-wide JAX settings of a benchmark run."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # keep every compile, however short, so a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
