"""Find an open-loop cell's knee: the highest arrival rate the system
sustains without a growing backlog. One set-up, then one stream per rate
(warm-up, window, drain, as a benchmark run has them), all in this
process (the benchmark's runs never call this).

    python3 bench/sweep.py --workload qwen05b.chat --seed 7 --seconds 40 \\
        --rates 0.5 0.65 0.8 0.95 --drain-limit 20

Prints one JSON line per rate: requests due in the window and how many
finished, TTFT and TPOT percentiles of those that did, output tokens/s
over the window, and the backlog: requests released but not yet admitted
(load included) as the window opens, at its middle and at its close.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
# libtpu would log under /tmp/tpu_logs, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--drain-limit", type=float, default=None,
                    help="seconds to wait after the window (default: the "
                    "mix's drain_limit_s)")
    args = ap.parse_args(argv)

    from harness import runner, spec, stats
    import run as bench_run
    cell = spec.load_cell(args.workload)
    if bench_run.device_ok(cell.chips) is None:
        return 2
    runner.env_flags()
    sv = runner.set_up(cell, args.seed)
    e = sv.engine
    for rate in args.rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        if args.drain_limit is not None:
            mix["drain_limit_s"] = args.drain_limit
        c = dataclasses.replace(cell, traffic=mix)
        marks = {}
        orig = e._stamp_arrivals

        def stamp(requests, step, _orig=orig, _marks=marks):
            try:
                _orig(requests, step)
            finally:
                now = time.perf_counter()
                waiting = sum(r.state == "queued"
                              for r in e._reqs[:e._next])
                for name, at in (("open", e.t0),
                                 ("middle", e.t0 + args.seconds / 2),
                                 ("close", e.t_end)):
                    if name not in _marks and now >= at:
                        _marks[name] = waiting

        e._stamp_arrivals = stamp
        try:
            run = runner.serve(sv, c, args.seed, args.seconds,
                               t_process=T_PROCESS)
        finally:
            del e._stamp_arrivals
        done = [r for r in e.released if r.done]
        print(json.dumps({
            "rate_per_s": rate,
            "due": len(e.released),
            "finished": len(done),
            "waiting_at_open": marks.get("open"),
            "waiting_at_middle": marks.get("middle"),
            "waiting_at_close": marks.get("close"),
            "ttft_p50_ms": stats.percentile(stats.ttft_s(done), 50) * 1e3,
            "ttft_p95_ms": stats.percentile(stats.ttft_s(done), 95) * 1e3,
            "tpot_p50_ms": stats.percentile(stats.tpot_s(done), 50) * 1e3,
            "tpot_p95_ms": stats.percentile(stats.tpot_s(done), 95) * 1e3,
            "output_tok_s": stats.rate(e.emits, e.t0, e.t_end),
            "lateness_p95_ms": stats.percentile(e.lateness_s, 95) * 1e3,
            "compiles_in_window": run.compiles_in_window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
