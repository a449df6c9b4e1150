"""Shared set-up of the benchmark's CPU tests: a cell made of a
configuration file and a traffic file of ``bench/``, cut to a size the
CPU serves in a second, through the real harness."""
from __future__ import annotations

import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import json  # noqa: E402

from harness import runner, spec  # noqa: E402

# name -> (configuration file, traffic mix)
CELLS = {"qwen05b.chat": ("qwen1.5-0.5b.S3A3E8", "chat")}

TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 176,
        "num_hidden_layers": 2, "vocab_size": 256,
        "torch_dtype": "float32",
        "serving": {"slots": 3, "block_size": 8, "prefill_budget": 8},
        "check": {"sample_requests": 3, "logit_gap_limit": 1e-3,
                  "shared_shortfall_limit": 0.1, "rep_rank_limit": 0.1,
                  "cluster_gain_limit": 1.2}}


def tiny_cell(name: str = "qwen05b.chat", **traffic) -> spec.Cell:
    """The named cell with small lengths (and any traffic keys given)."""
    config, mix = CELLS[name]
    cell = spec.Cell(
        name=name, chips=1, traffic_name=mix, end_to_end=[], per_layer=[],
        config=json.loads((BENCH / "configs" / f"{config}.json").read_text()),
        traffic=json.loads((BENCH / "traffic" / f"{mix}.json").read_text()))
    t = dict(cell.traffic,
             prompt_tokens={"median": 12, "sigma": 0.6, "min": 4,
                            "max": 24},
             output_tokens={"median": 6, "sigma": 0.6, "min": 2,
                            "max": 10},
             rate_per_s=8.0, warmup_s=0.5, drain_limit_s=20.0)
    t.update(traffic)
    cell.traffic = t
    return cell


def serve(cell: spec.Cell, seed: int = 3, seconds: float = 1.0, *,
          use_kernel: bool = False, **overrides) -> runner.Run:
    """One run of ``cell`` at the TINY size, on the CPU."""
    return runner.serve_window(cell, seed, seconds,
                               t_process=time.perf_counter(),
                               use_kernel=use_kernel,
                               config_overrides=dict(TINY, **overrides))
