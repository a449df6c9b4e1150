"""The trace-to-metric reduction, on a trace recorded on a TPU v5e: seven
fused steps of ``qwen05b.chat`` (rows 52-76, ``grouped_pallas``)."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import benchtest_util  # noqa: F401  (puts bench/ and src/ on the path)
from harness import peaks, readers, trace
from harness.release import Dispatch

TRACE = Path(__file__).parent / "data" / "qwen05b.chat.xplane.pb.gz"


@pytest.fixture(scope="module")
def red():
    return trace.reduce(str(TRACE))


def test_union_counts_nested_ops_once():
    busy, gaps = trace._union([(0, 100), (10, 20), (30, 40), (150, 160),
                               (155, 170)])
    assert busy == 120 and gaps == [(100, 150)]


def test_op_key_keeps_name_and_result_type():
    assert trace.op_key("%copy.95 = bf16[1,3073,16,16,64]{4,3,2,1,0:T(8)} "
                        "copy(bf16[1] %x)") == "%copy bf16[1,3073,16,16,64]"
    assert trace.op_key("%while.51") == "%while"


def test_reduction_of_the_recorded_trace(red):
    assert [d["name"] for d in red["devices"]] == ["/device:TPU:0"]
    n, ns = trace.module_time(red, lambda k: readers.STEP_PROGRAM in k)
    assert n == 7 and ns / n / 1e6 == pytest.approx(205.63, abs=0.01)
    assert trace.busy_s(red) == pytest.approx(1.4394, abs=1e-4)
    top = trace.top_ops(red, 3)
    assert top[0][0] == "%paged_attn_decode bf16[76,16,1,64]"
    assert all(not k.startswith("%while") for k, _ in top)
    gmm = trace.op_time_ns(red, lambda k: k.startswith(
        readers.MOE_GMM_KERNEL + " "))
    assert gmm == pytest.approx(3.251e6, rel=1e-3)
    # the pool's copies, slices and updates: 30.6% of device time
    assert trace.pool_share(red, "3073,16") == pytest.approx(0.306, abs=1e-3)
    gaps = trace.idle_gaps(red, 3)
    assert [g[0] for g in gaps] == ["bench.plan_dispatch"] * 3
    assert gaps[0][1] == pytest.approx(1.915e-3, rel=1e-3)


def test_roofline_and_mfu_stay_under_the_peak(red):
    """The traced steps' live rows (as the engine logged them), read
    against the kernel time and the window the trace holds."""
    rows = [76, 76, 76, 76, 72, 72, 52]
    ds = [Dispatch(t0=0.0, t1=0.0, padded=r, live=r - 1,
                   backend="grouped_pallas", occupied=32, enqueue_s=0.0,
                   ctx=[400] * (r - 1), logit_rows=32) for r in rows]
    config = benchtest_util.spec.load_cell("qwen05b.chat").config
    run = SimpleNamespace(
        trace=red, peaks=peaks.peaks("TPU v5 lite"),
        config=config,
        engine=SimpleNamespace(dispatches=ds, trace_dispatches=[0, 7],
                               trace_t=[0.0, 1.45]))
    roof = readers.moe_gmm_roofline(run)
    mfu = readers.step_mfu(run)
    assert 10.0 < roof < 100.0
    assert 0.0 < mfu < 100.0
    assert readers.device_idle_share(run) == pytest.approx(
        100 * (1 - 1.4394 / 1.45), abs=0.05)
