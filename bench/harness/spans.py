"""The serving engine's own host spans and request stamps, read for the
per-layer metrics.

Under ``jax.profiler.start_trace`` the engine's overlapped loop marks
each phase with a ``jax.profiler`` annotation (``engine.step`` per loop
iteration, ``engine.<phase>`` inside it, each with ``step=<n>``) and
each Python garbage collection with ``host.gc``. The profiler writes
them on the host plane of the same ``.xplane.pb`` as the device ops, on
one clock. Requests carry ``admit_t``, the host clock of the dispatch
that first carried one of their chunks.

A program without these spans or stamps leaves nothing to read: every
reader then returns None, and the harness leaves the metric out.
"""
from __future__ import annotations

import gzip
import warnings

import jax

from harness import readers, stats, trace

ENGINE_PREFIX = "engine."
GC = "host.gc"
ENQUEUE = "engine.enqueue"
READBACK = "engine.readback"
# the spans in which the host waits on the device: a device gap under
# one of them is not the host's doing
WAITS = (ENQUEUE, READBACK)


def read(path: str) -> list:
    """(name, start_ns, end_ns, stats) of every ``engine.*`` and
    ``host.gc`` span in the profile at ``path`` (``.xplane.pb``, or
    gzipped), by start."""
    if path.endswith(".gz"):
        with gzip.open(path) as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    out = []
    with warnings.catch_warnings():
        # the stats' pybind type warns on every read under Python 3.12
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(ENGINE_PREFIX) or name == GC:
                        out.append((name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    out.sort(key=lambda s: (s[1], -s[2]))
    return out


def of_run(run) -> list:
    """The spans of the run's profile, read once and kept on the run;
    empty when the run was not traced."""
    spans = getattr(run, "engine_spans", None)
    if spans is None:
        plan = getattr(run.engine, "trace", None)
        spans = []
        if plan is not None and run.trace is not None:
            try:
                spans = read(trace.find(plan.directory))
            except FileNotFoundError:
                pass
        run.engine_spans = spans
    return spans


def segments(spans) -> list:
    """The host timeline the spans cover, cut into disjoint
    ``(start, end, name)`` stretches, each named by its innermost span:
    of the spans open there, the one that opened last."""
    points = sorted({t for _, s, e, _ in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    out, active, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            active.append(by_start[i])
            i += 1
        active = [sp for sp in active if sp[2] > a]
        if active:
            inner = max(active, key=lambda sp: (sp[1], -sp[2]))
            out.append((a, b, inner[0]))
    return out


def _overlap_ns(xs, ys) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        lo = max(xs[i][0], ys[j][0])
        hi = min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def host_bound_ns(gaps, spans) -> float:
    """Length of the device gaps in which the host's innermost span is
    not a wait on the device (``WAITS``); time outside every span
    counts."""
    waits = [(a, b) for a, b, name in segments(spans) if name in WAITS]
    total = sum(e - s for s, e in gaps)
    return total - _overlap_ns(sorted(gaps), waits)


# ------------------------------------------------------------- metrics

def mean_ms(run, name: str):
    """Mean duration of the traced ``name`` spans."""
    ds = [e - s for n, s, e, _ in of_run(run) if n == name]
    return sum(ds) / len(ds) / 1e6 if ds else None


def host_bound_idle_share(run):
    """Share of the traced window in which no device op runs and the
    host is not waiting on the device."""
    spans = of_run(run)
    if not spans or not run.trace["devices"]:
        return None
    gaps = run.trace["devices"][0]["gaps"]
    return 100.0 * host_bound_ns(gaps, spans) / 1e9 / \
        readers.traced_window_s(run)


def _stamped(run) -> list:
    """The window's requests, if the program stamps admissions."""
    released = run.engine.released
    if not released or not hasattr(released[0], "admit_t"):
        return []
    return released


def queue_wait_ms(run, q):
    """Nearest-rank q-th percentile of due time to admission; a request
    never admitted is a miss."""
    rs = _stamped(run)
    if not rs:
        return None
    return stats.percentile([r.admit_t - r.arrival_t if r.admit_t >= 0
                             else stats.INF for r in rs], q) * 1e3


def prefill_ms(run, q):
    """Nearest-rank q-th percentile of admission to first emitted token;
    a request without one is a miss."""
    rs = _stamped(run)
    if not rs:
        return None
    return stats.percentile([r.first_token_t - r.admit_t
                             if r.first_token_t >= 0 and r.admit_t >= 0
                             else stats.INF for r in rs], q) * 1e3
