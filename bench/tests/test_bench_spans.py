"""The serving engine's host spans, read back from a profile recorded on
the CPU, and the arithmetic of the span and stamp metrics on synthetic
intervals."""
import gc
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import benchtest_util  # noqa: F401  (puts bench/ and src/ on the path)
from harness import readers, runner, spans, stats, trace
from harness.release import TracePlan
from repro.config import override
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving import Request, ServingEngine
from repro.serving import engine as engine_mod

PHASES = ("engine.arrivals", "engine.plan", "engine.rows", "engine.stage",
          "engine.enqueue", "engine.finish")        # a dispatch's own step
LAGGED = ("engine.readback", "engine.emit")         # a later loop step


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A small overlapped paged engine run under the JAX profiler (after
    a run that compiled every shape), and the spans read back."""
    cfg = override(get_smoke_config("qwen1.5-0.5b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size, 6 + 5 * i)],
                    max_new=5, arrival=float(2 * i)) for i in range(4)]
    engine = ServingEngine(model, params, max_slots=2, max_len=32,
                           prefill_bucket=8, max_prefill_tokens=8,
                           paged=True, block_size=4, overlap=True)
    engine.run(reqs)
    d = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(d))
    try:
        rep = engine.run(reqs)
    finally:
        jax.profiler.stop_trace()
    return engine, rep, spans.read(trace.find(str(d)))


def _named(sp, name):
    return [s for s in sp if s[0] == name]


def test_one_step_span_per_loop_iteration(profiled):
    _, rep, sp = profiled
    steps = _named(sp, "engine.step")
    assert [s[3]["step_num"] for s in steps] == list(range(rep.steps))
    assert all(a[2] <= b[1] for a, b in zip(steps, steps[1:]))


def test_one_enqueue_per_dispatch_with_its_step(profiled):
    engine, _, sp = profiled
    logged = [row[0] for row in engine.backend_log]
    assert logged
    assert [s[3]["step"] for s in _named(sp, "engine.enqueue")] == logged
    # every dispatch is read back once, after it was enqueued
    assert sorted(s[3]["step"] for s in _named(sp, "engine.readback")) \
        == logged
    enq = {s[3]["step"]: s for s in _named(sp, "engine.enqueue")}
    for s in _named(sp, "engine.readback"):
        assert s[1] >= enq[s[3]["step"]][2]


def test_phase_spans_nest_inside_their_step(profiled):
    _, _, sp = profiled
    steps = _named(sp, "engine.step")
    seen = set()
    for name, s, e, st in sp:
        if name not in PHASES + LAGGED:
            continue
        outer = [x for x in steps if x[1] <= s and e <= x[2]]
        assert len(outer) == 1, (name, st)
        n = outer[0][3]["step_num"]
        # a dispatch's phases run in its own loop step; its readback and
        # emission in the next one (or the same, when the loop drains)
        assert n == st["step"] if name in PHASES else n >= st["step"]
        seen.add(name)
    assert seen == set(PHASES + LAGGED)


def test_gc_spans_mark_collections_and_go_away():
    before = list(gc.callbacks)
    with engine_mod._gc_spans():
        assert len(gc.callbacks) == len(before) + 1
        gc.collect()                       # no profile: a no-op span
    assert gc.callbacks == before


def test_gc_span_is_recorded(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with engine_mod._gc_spans():
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    got = _named(spans.read(trace.find(str(tmp_path))), spans.GC)
    assert got and got[-1][3]["generation"] == 2


# ---------------------------------------------------- synthetic intervals

# step [0, 100): plan [10, 20), enqueue [30, 60) holding a gc [40, 45),
# readback [70, 90); nothing after 100
SPANS = [("engine.step", 0, 100, {}), ("engine.plan", 10, 20, {}),
         ("engine.enqueue", 30, 60, {}), ("host.gc", 40, 45, {}),
         ("engine.readback", 70, 90, {})]
GAPS = [(5, 15), (35, 50), (80, 120)]


def test_innermost_span_names_each_stretch():
    segs = spans.segments(SPANS)
    assert segs == [(0, 10, "engine.step"), (10, 20, "engine.plan"),
                    (20, 30, "engine.step"), (30, 40, "engine.enqueue"),
                    (40, 45, "host.gc"), (45, 60, "engine.enqueue"),
                    (60, 70, "engine.step"), (70, 90, "engine.readback"),
                    (90, 100, "engine.step")]


def test_host_bound_time_leaves_out_the_waits():
    # (5, 15): step then plan, 10; (35, 50): only the gc inside the
    # enqueue, 5; (80, 120): step 90-100 and outside every span, 30
    assert spans.host_bound_ns(GAPS, SPANS) == 45
    assert spans.host_bound_ns([(30, 40), (70, 90)], SPANS) == 0


def _run(gaps, sp, window_s, released=()):
    return SimpleNamespace(
        trace={"devices": [{"gaps": gaps}]}, engine_spans=sp,
        engine=SimpleNamespace(trace_t=[1.0, 1.0 + window_s],
                               released=list(released)))


def test_host_bound_share_is_within_the_idle_share():
    run = _run(GAPS, SPANS, 200e-9)
    share = spans.host_bound_idle_share(run)
    assert share == pytest.approx(100 * 45 / 200)
    idle = 100 * sum(e - s for s, e in GAPS) / 200
    assert share <= idle
    assert spans.mean_ms(run, spans.ENQUEUE) == pytest.approx(30e-6)


def test_readers_find_nothing_in_a_program_without_spans_or_stamps():
    run = _run(GAPS, [], 200e-9,
               released=[SimpleNamespace(arrival_t=0.0, first_token_t=1.0)])
    assert spans.host_bound_idle_share(run) is None
    assert spans.mean_ms(run, spans.READBACK) is None
    assert spans.queue_wait_ms(run, 95) is None
    assert spans.prefill_ms(run, 95) is None
    # a run that was not traced reads no profile
    untraced = SimpleNamespace(trace=None, engine=SimpleNamespace(
        trace=None))
    assert spans.of_run(untraced) == []


def test_stamp_percentiles_count_misses():
    def req(arrival, admit, first):
        return SimpleNamespace(arrival_t=arrival, admit_t=admit,
                               first_token_t=first)
    rs = [req(0.0, 0.1 * i, 0.1 * i + 0.5) for i in range(19)]
    run = _run([], [], 1.0, released=rs)
    assert spans.queue_wait_ms(run, 95) == pytest.approx(1800.0)
    assert spans.prefill_ms(run, 95) == pytest.approx(500.0)
    # one request never admitted: the queue tail and prefill tail miss
    run.engine.released.append(req(0.0, -1.0, -1.0))
    assert spans.queue_wait_ms(run, 95) == pytest.approx(1800.0)
    assert spans.queue_wait_ms(run, 100) == stats.INF
    assert spans.prefill_ms(run, 100) == stats.INF


# ------------------------------------------------- through the harness

@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A tiny window of the chat cell with a profile of its middle, read
    as ``bench/run.py`` reads it."""
    cell = benchtest_util.tiny_cell("qwen05b.chat", rate_per_s=6.0)
    plan = TracePlan(start_s=0.2, seconds=0.5,
                     directory=str(tmp_path_factory.mktemp("window")))
    run = runner.serve_window(cell, 7, 1.0, t_process=time.perf_counter(),
                              trace_plan=plan, use_kernel=False,
                              config_overrides=benchtest_util.TINY)
    run.trace = trace.reduce(trace.find(plan.directory))
    return run


def test_harness_reads_the_engine_spans_of_its_traced_dispatches(
        traced_run):
    run = traced_run
    ds = readers.traced_dispatches(run)
    assert ds, "no dispatch was traced"
    enq = _named(spans.of_run(run), spans.ENQUEUE)
    # the profile holds exactly the traced dispatches' enqueues, and each
    # span encloses the call the harness times
    assert len(enq) == len(ds)
    for (_, s, e, _), d in zip(enq, ds):
        assert (e - s) / 1e9 >= d.enqueue_s - 1e-6
    harness_ms = sum(d.enqueue_s for d in ds) / len(ds) * 1e3
    assert spans.mean_ms(run, spans.ENQUEUE) == pytest.approx(
        harness_ms, abs=0.05)
    assert spans.mean_ms(run, spans.READBACK) > 0
    # the CPU trace has no device plane: nothing to split
    assert spans.host_bound_idle_share(run) is None


def test_stamp_tails_stay_within_the_ttft_tail(traced_run):
    ttft = readers.ttft_ms(traced_run, 95)
    assert 0 <= spans.queue_wait_ms(traced_run, 95) <= ttft
    assert 0 < spans.prefill_ms(traced_run, 95) <= ttft
