"""The arithmetic behind each metric file in ``bench/metrics/``.

A reader takes the finished ``Run`` and returns its number, or None when
the run holds nothing for it to read (no trace, no kernel call): the
harness then leaves the metric out of the result line.
"""
from __future__ import annotations

from harness import flops, stats, trace
from reference import cmoe_gqa

STEP_PROGRAM = "_step_fused_paged_impl"
MOE_GMM_KERNEL = "%moe_gmm_ragged"    # the Pallas kernel's op name


def window_dispatches(run):
    e = run.engine
    return [d for d in e.dispatches if e.t0 <= d.t0 < e.t_end]


def traced_dispatches(run):
    i0, i1 = run.engine.trace_dispatches
    if i0 is None or i1 is None or run.trace is None:
        return []
    return run.engine.dispatches[i0:i1]


def traced_window_s(run) -> float:
    t0, t1 = run.engine.trace_t
    return t1 - t0


# ----------------------------------------------------------- end to end

def ttft_ms(run, q):
    return stats.percentile(stats.ttft_s(run.engine.released), q) * 1e3


def tpot_ms(run, q):
    return stats.percentile(stats.tpot_s(run.engine.released), q) * 1e3


# ------------------------------------------------------------ per layer

def host_plan_ms(run):
    """Host time per dispatch outside the enqueue of the jitted step."""
    ds = window_dispatches(run)
    if not ds:
        return None
    return sum(d.t1 - d.t0 - d.enqueue_s for d in ds) / len(ds) * 1e3


def enqueue_ms(run):
    """Host time per dispatch inside the enqueue of the jitted step
    (printed on an earlier line; the enqueue waits when the device has
    no room for the step's output)."""
    ds = window_dispatches(run)
    if not ds:
        return None
    return sum(d.enqueue_s for d in ds) / len(ds) * 1e3


def padded_row_share(run):
    ds = window_dispatches(run)
    if not ds:
        return None
    return 100.0 * (1 - sum(d.live for d in ds) / sum(d.padded for d in ds))


def step_device_ms(run):
    if run.trace is None:
        return None
    n, ns = trace.module_time(run.trace, lambda k: STEP_PROGRAM in k)
    return ns / n / 1e6 if n else None


def _dims(run):
    cfg = run.config
    dm = cmoe_gqa.dims(cfg)
    return dm, flops.cmoe_dims(dm, cfg["cmoe"])


def step_mfu(run):
    ds = traced_dispatches(run)
    if not ds:
        return None
    dm, cm = _dims(run)
    total = sum(flops.row_flops(dm, cm, c, head=False)
                for d in ds for c in d.ctx)
    total += sum(d.logit_rows for d in ds) * 2.0 * dm["d"] * dm["V"]
    return 100.0 * total / (traced_window_s(run) *
                            run.peaks["bf16_flops_per_s"])


def moe_gmm_roofline(run):
    ds = [d for d in traced_dispatches(run) if d.backend == "grouped_pallas"]
    if not ds:
        return None
    ns = trace.op_time_ns(run.trace,
                          lambda k: k.startswith(MOE_GMM_KERNEL + " "))
    if ns <= 0:
        return None
    dm, cm = _dims(run)
    least = sum(flops.least_time(*flops.moe_gmm_needs(dm, cm, d.live),
                                 run.peaks)[0] for d in ds) * dm["L"]
    return 100.0 * least / (ns / 1e9)


def device_idle_share(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    return 100.0 * (1 - trace.busy_s(run.trace) / traced_window_s(run))
