"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; ``harness.spec`` finds their files. With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profile of a few seconds in the middle of
the window. Either way the run is checked against the plain float32
reference (the conversion's partition against the reference's own
activation profile, and a sample of the served requests token by
token), and the numbers compared are printed beside their limits: last
on standard error, and last in the result line. The run refuses (exit 2,
no result line) unless JAX's default backend is a TPU whose
``device_kind`` has published peaks and which has as many chips as the
cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
# libtpu would log under /tmp/tpu_logs, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# the profile covers this stretch of the window (fractions of it)
TRACE_START, TRACE_LEN = 0.5, 0.08


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def device_ok(chips: int):
    """(device dict, peaks) of an attached TPU fit for the cell, or None."""
    import jax
    from harness.peaks import PEAKS
    if jax.default_backend() != "tpu":
        log(f"bench: JAX found no TPU (default backend "
            f"{jax.default_backend()!r}); refusing to run elsewhere")
        return None
    devs = jax.devices()
    kind = devs[0].device_kind
    if kind not in PEAKS:
        log(f"bench: no published peaks for device kind {kind!r}")
        return None
    if len(devs) < chips:
        log(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
        return None
    return PEAKS[kind]


def result(run, metrics: list, checks: dict, *, traced: bool) -> dict:
    from harness import spec, trace
    from harness.stats import INF
    vals = {}
    for m in metrics:
        v = spec.metric_reader(m["name"])(run)
        if v is None:
            continue
        vals[m["name"]] = {"value": float(v) if v != INF else None,
                           "unit": m["unit"]}
    released = run.engine.released
    out = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": len(released),
           "failed": sum(1 for r in released if not r.done),
           "metrics": vals,
           "device": dict(run.device,
                          memory_peak_bytes=run.memory_peak_bytes)}
    if traced and run.trace is not None and run.trace["devices"]:
        t0, t1 = run.engine.trace_t
        out["device"]["busy_s"] = trace.busy_s(run.trace)
        out["device"]["window_s"] = t1 - t0
        out["breakdown"] = {"device_ops": trace.top_ops(run.trace),
                            "idle_gaps": trace.idle_gaps(run.trace)}
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return out


def checks_of(run, chk: dict) -> dict:
    """Every number ``correct`` compares, with its limit and verdict.
    ``cluster_gain`` has a lower limit; every other number an upper."""
    lim = run.config["check"]
    part = chk["partition"]
    failed = sum(1 for r in run.engine.released if not r.done)

    def upper(v, limit):
        return {"value": v, "limit": limit, "ok": v <= limit}

    return {
        "partition_invalid": upper(part["invalid"], 0),
        "shared_shortfall": upper(part["shared_shortfall"],
                                  float(lim["shared_shortfall_limit"])),
        "rep_rank": upper(part["rep_rank"], float(lim["rep_rank_limit"])),
        "cluster_gain": {"value": part["cluster_gain"],
                         "limit": float(lim["cluster_gain_limit"]),
                         "ok": part["cluster_gain"] >=
                         float(lim["cluster_gain_limit"])},
        "logit_gap": upper(chk["served"], float(lim["logit_gap_limit"])),
        "compared_tokens": {"value": chk["tokens"], "limit": 1,
                            "ok": chk["tokens"] >= 1},
        "unfinished": upper(failed, 0),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import spec
    cell = spec.load_cell(args.workload)
    pk = device_ok(cell.chips)
    if pk is None:
        return 2

    from harness import readers, runner, stats, trace
    from harness.release import TracePlan
    runner.env_flags()
    plan = None
    if args.trace:
        plan = TracePlan(start_s=TRACE_START * args.seconds,
                         seconds=TRACE_LEN * args.seconds,
                         directory=runner.trace_dir(ROOT, cell.name))
    run = runner.serve_window(cell, args.seed, args.seconds,
                              t_process=T_PROCESS, trace_plan=plan)
    run.peaks = pk
    e = run.engine
    log(f"[bench] {cell.name} seed {args.seed}: set-up {run.setup_s:.2f}s "
        f"(conversion {run.convert_s:.2f}s), {run.compiles_setup} compiles "
        f"in set-up ({run.compile_hits} from the persistent cache), "
        f"{run.compiles_in_window} inside the window")
    log(f"[bench] {len(e.released)} requests due in the window "
        f"({e.load_released} more as load: warm-up and drain); release "
        f"lateness p50/p95 {stats.percentile(e.lateness_s, 50) * 1e3:.3f}/"
        f"{stats.percentile(e.lateness_s, 95) * 1e3:.3f} ms; "
        f"{len(e.dispatches)} dispatches, enqueue "
        f"{readers.enqueue_ms(run)} ms per window dispatch; stopped "
        f"{e.t_stop - e.t_end:.1f} s after the window closed")
    if plan is not None:
        run.trace = trace.reduce(trace.find(plan.directory))
        if run.trace["devices"]:
            i0, i1 = e.trace_dispatches
            share = trace.pool_share(run.trace, e.kv_dims)
            log(f"[trace] {i1 - i0} steps traced; KV pool copies, slices "
                f"and updates outside the kernels: {100 * share:.1f}% of "
                f"device time")
    chk = runner.check(run)
    checks = checks_of(run, chk)
    metrics = cell.per_layer if args.trace else cell.end_to_end
    out = result(run, metrics, checks, traced=bool(args.trace))
    log(f"[check] {chk['requests']} requests, {chk['tokens']} served "
        f"tokens compared with the float32 reference")
    for k, c in checks.items():
        log(f"check {k} = {c['value']} (limit {c['limit']}): "
            f"{'ok' if c['ok'] else 'FAILED'}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
