"""Readings that set a cell's check limits, all seeds in one process so
the compiled programs are shared (the benchmark's runs never call this).

Default: for each seed, one stream of the cell's own traffic at its own
sizes, then the widest logit gap of the served tokens (the program's
reading) and of the tokens the fp8 control puts first at the same
positions (the control's reading), both against the float32 reference.

``--partition``: for each seed, the program's conversion only, and the
readings of its partition against the reference's activation profile
(``reference.partition_readings``); on the first three seeds also those
of planted partitions: ``uniform`` (neurons taken in index order),
``random`` (a random permutation), and ``random_routed`` (the program's
shared experts, a random grouping of the routed neurons, each expert's
representative the neuron nearest its centroid).

    python3 bench/control.py --workload qwen05b.chat --seconds 15 \\
        --seeds 101 102 103
    python3 bench/control.py --workload qwen05b.chat --partition \\
        --seeds 101 102 103

Prints one JSON line per seed, then the lower and upper readings.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
# libtpu would log under /tmp/tpu_logs, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def planted(parts: list, config: dict, profile: list, seed: int) -> dict:
    """The planted partitions of one seed, layer by layer."""
    c = config["cmoe"]
    dff = int(config["intermediate_size"])
    n_e, n_s = int(c["num_experts"]), int(c["num_shared"])
    m = dff // n_e
    rng = np.random.default_rng([int(seed), 3])

    def split(order):
        routed = np.sort(order[n_s * m:].reshape(n_e - n_s, m), axis=1)
        return {"shared_idx": np.sort(order[:n_s * m]),
                "routed_idx": routed, "rep_idx": routed[:, 0]}

    out = {"uniform": [split(np.arange(dff))] * len(parts),
           "random": [split(rng.permutation(dff)) for _ in parts],
           "random_routed": []}
    for p, a in zip(parts, profile):
        pool = rng.permutation(np.asarray(p["routed_idx"]).reshape(-1))
        routed = np.sort(pool.reshape(n_e - n_s, m), axis=1)
        feats = a.T.astype(np.float32)
        reps = [e[np.argmin(((feats[e] - feats[e].mean(0)) ** 2).sum(1))]
                for e in routed]
        out["random_routed"].append({"shared_idx": p["shared_idx"],
                                     "routed_idx": routed,
                                     "rep_idx": np.array(reps)})
    return out


def layer_gains(ref, profile, parts, config) -> list:
    """``cluster_gain`` of each layer alone."""
    return [round(ref.partition_readings([a], [p], config)["cluster_gain"],
                  4) for a, p in zip(profile, parts)]


def partition_mode(cell, seeds) -> None:
    from harness import runner, spec
    ref = spec.reference_module(cell.config)
    config = cell.config
    prog, plants = [], {}
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        _, params, parts, convert_s = runner.convert(config, seed)
        del params
        dense = ref.make_params(config, seed)
        profile = ref.activation_profile(dense, config,
                                         ref.calib_tokens(config, seed))
        del dense
        r = ref.partition_readings(profile, parts, config)
        prog.append(r)
        line = {"seed": seed, "program": r, "convert_s": convert_s}
        if i < 3:
            line["layer_gain"] = {"program": layer_gains(ref, profile,
                                                          parts, config)}
            for name, pp in planted(parts, config, profile, seed).items():
                line[name] = ref.partition_readings(profile, pp, config)
                plants.setdefault(name, []).append(line[name])
                line["layer_gain"][name] = layer_gains(ref, profile, pp,
                                                       config)
        line["seconds"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
    summary = {"seeds": len(seeds)}
    for key, worst in (("shared_shortfall", max), ("rep_rank", max),
                       ("cluster_gain", min), ("invalid", max)):
        best = min if worst is max else max
        summary[key] = {"lower": worst(r[key] for r in prog)}
        for name, rs in plants.items():
            summary[key][name] = best(r[key] for r in rs)
    print(json.dumps(summary), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--partition", action="store_true")
    args = ap.parse_args(argv)

    from harness import runner, spec
    from harness.compile_log import CompileLog
    import run as bench_run
    cell = spec.load_cell(args.workload)
    if bench_run.device_ok(cell.chips) is None:
        return 2
    runner.env_flags()
    if args.partition:
        partition_mode(cell, args.seeds)
        return 0
    compiles = CompileLog()
    served, control = [], []
    for seed in args.seeds:
        t = time.perf_counter()
        run = runner.serve_window(cell, seed, args.seconds,
                                  t_process=t, compiles=compiles)
        chk = runner.check(run, control=True)
        served.append(chk["served"])
        control.append(chk["control"])
        print(json.dumps({"seed": seed, **chk,
                          "attempted": len(run.engine.released),
                          "failed": sum(1 for r in run.engine.released
                                        if not r.done),
                          "seconds": time.perf_counter() - t}), flush=True)
    print(json.dumps({"lower_reading": max(served),
                      "upper_reading": min(control),
                      "seeds": len(args.seeds)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
