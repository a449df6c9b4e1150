"""The plain reference against the served path, at a CPU size.

In float32 the served tokens are the reference's best (the widest gap is
rounding) and the conversion's partition is the reference's own; in the
configuration's bf16 the served path stays well below what the fp8
control — the reference computed in the next precision down — reads on
the same prompts and tokens, and the control fails the run's check.
"""
import pytest

from benchtest_util import TINY, serve, tiny_cell
from harness import runner
import run as bench_run


@pytest.mark.parametrize("cell", ["qwen05b.chat"])
def test_sound_float32_run_matches_the_reference(cell):
    run = serve(tiny_cell(cell), seed=21, seconds=1.0)
    chk = runner.check(run)
    assert chk["requests"] >= 2 and chk["tokens"] > 10
    assert chk["served"] <= TINY["check"]["logit_gap_limit"], chk
    checks = bench_run.checks_of(run, chk)
    assert all(c["ok"] for c in checks.values()), checks
    part = chk["partition"]
    assert part["invalid"] == 0 and part["shared_shortfall"] == 0.0
    assert part["rep_rank"] == 0.0 and part["cluster_gain"] > 1.2


@pytest.fixture(scope="module")
def bf16_check():
    run = serve(tiny_cell(), seed=8, seconds=1.5,
                torch_dtype="bfloat16", hidden_size=128,
                intermediate_size=352, vocab_size=512)
    return run, runner.check(run, control=True)


@pytest.mark.parametrize("cell", ["qwen05b.chat"])
def test_fp8_control_reads_above_the_bf16_served_path(cell, bf16_check):
    _, chk = bf16_check
    assert chk["tokens"] > 10
    assert chk["control"] > 3 * chk["served"], chk


def test_fp8_control_fails_the_check(bf16_check):
    """The control's reading, put through the run's own comparison with
    the bf16 limit its readings set, is not correct."""
    run, chk = bf16_check
    run.config = dict(run.config, check=dict(
        run.config["check"], logit_gap_limit=2 * chk["served"]))
    assert bench_run.checks_of(run, chk)["logit_gap"]["ok"]
    ctl = bench_run.checks_of(run, dict(chk, served=chk["control"]))
    assert not ctl["logit_gap"]["ok"], ctl
