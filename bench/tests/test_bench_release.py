"""The wall-clock release adapter, through the real serving engine (the
qwen smoke sizes in float32, Pallas kernels in interpret mode)."""
import pytest

from benchtest_util import serve, tiny_cell
from harness import readers, stats

SECONDS = 1.0


@pytest.fixture(scope="module")
def open_run():
    # arrivals at 6/s: 0.5 s of warm-up, a window of 1 s, then the tail
    return serve(tiny_cell("qwen05b.chat", rate_per_s=6.0), seed=5,
                 seconds=SECONDS, use_kernel=True, num_key_value_heads=4)


def test_open_loop_releases_on_due_times_and_serves_nothing_later(open_run):
    e = open_run.engine
    assert open_run.use_kernel
    due = dict(zip((r.rid for r in e._reqs), e._due_s))
    assert e.released, "nothing was due in the window"
    for r in e.released:
        assert 0 <= due[r.rid] < SECONDS
        # arrival_t is the DUE time on the host clock, not the release
        assert r.arrival_t == pytest.approx(e.t0 + due[r.rid], abs=1e-9)
        assert r.done and r.first_token_t > r.arrival_t
    assert all(x >= 0 for x in e.lateness_s)
    measured = {r.rid for r in e._measured}
    assert {r.rid for r in e.released} == measured
    # the window's requests are those due in it, and none other
    assert measured == {r.rid for r in e._reqs
                        if 0 <= due[r.rid] < SECONDS}
    late = [r for r in e._reqs if due[r.rid] >= SECONDS]
    assert late, "the traffic never ran past the window"
    # nothing due after the run stopped was served
    assert all(not r.generated for r in late
               if e.t0 + due[r.rid] > e.t_stop)
    # TTFT is measured from due time: it includes the release lateness
    for r, lag in zip(e.released, e.lateness_s):
        assert r.first_token_t - r.arrival_t >= lag
    assert readers.ttft_ms(open_run, 95) == pytest.approx(
        stats.percentile([(r.first_token_t - r.arrival_t) * 1e3
                          for r in e.released], 95))
    assert open_run.compiles_in_window == 0


def test_window_opens_on_a_loaded_engine_and_stops_when_its_requests_end(
        open_run):
    e = open_run.engine
    due = dict(zip((r.rid for r in e._reqs), e._due_s))
    warm = [r for r in e._reqs if due[r.rid] < 0]
    assert warm and all(r.rid not in {x.rid for x in e.released}
                        for r in warm)
    # set-up ends at the first release, the window opens a warm-up later
    assert e.t_start == pytest.approx(e.t0 + due[warm[0].rid])
    assert e.t0 - e.t_start == pytest.approx(-due[warm[0].rid])
    assert open_run.setup_s > 0
    # warm-up requests were in the engine before the window opened
    assert all(r.arrival_t < e.t0 for r in warm)
    assert e.load_released >= len(warm)
    # the run stopped once every request due in the window had finished
    last = max(r.last_token_t for r in e.released)
    assert e.t_stop >= max(last, e.t_end)
    assert e.t_stop < e.t_end + e.drain_limit_s
    # dispatches of the window are those begun inside it
    ds = readers.window_dispatches(open_run)
    assert ds and all(e.t0 <= d.t0 < e.t_end for d in ds)
