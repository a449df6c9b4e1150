"""Reduction of a JAX profiler trace to the numbers the per-layer
metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes
under ``<dir>/plugins/profile/<time>/``; ``jax.profiler.ProfileData``
reads it with nothing but JAX. Device planes are named ``/device:TPU:<n>``:
their ``XLA Ops`` line holds one event per operation that ran, named by
its HLO text (a Pallas kernel is ``%<kernel name>.<n> = ...
custom-call(...)``; the layer scan is a ``%while`` whose event spans the
ops it runs), their ``XLA Modules`` line one event per jitted program
run (``jit_<function>(<hash>)``). The host plane holds the benchmark's own
``bench.*`` spans (``jax.profiler.TraceAnnotation``), on the same clock.
"""
from __future__ import annotations

import glob
import gzip
import os
from collections import defaultdict

import jax

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
# control-flow ops whose event spans the ops they run (the layer scan's
# while loop): left out of the time by op name, never out of busy time
CONTAINERS = ("%while", "%conditional", "%call")


def find(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def op_key(hlo: str) -> str:
    """An op event's name is its HLO text; keep the op's name without its
    number and its result type: ``%copy bf16[1,3073,16,16,64]``."""
    head, _, rest = hlo.partition(" = ")
    return head.rsplit(".", 1)[0] + (" " + rest.split("{")[0][:80]
                                     if rest else "")


def _union(intervals):
    """Total length and the gaps of a set of [start, end) intervals."""
    total, gaps, cur_s, cur_e = 0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s > cur_e:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def reduce(path: str) -> dict:
    """Per-device busy time and idle gaps, device time by op name, jitted
    programs by name (count and time), and the host's bench spans, of the
    trace at ``path`` (``.xplane.pb``, or gzipped). Times are in ns."""
    if path.endswith(".gz"):
        with gzip.open(path) as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            if not ops:
                continue
            busy, gaps = _union((s, s + d) for _, s, d in ops)
            by_op = defaultdict(float)
            for name, _, d in ops:
                by_op[op_key(name)] += d
            by_mod = defaultdict(lambda: [0, 0.0])
            for name, _, d in mods:
                by_mod[name][0] += 1
                by_mod[name][1] += d
            devices.append({"name": plane.name, "busy_ns": busy,
                            "first_ns": min(s for _, s, _ in ops),
                            "last_ns": max(s + d for _, s, d in ops),
                            "gaps": gaps, "by_op": dict(by_op),
                            "by_module": {k: tuple(v)
                                          for k, v in by_mod.items()}})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return {"devices": devices, "spans": sorted(spans, key=lambda x: x[1])}


def op_time_ns(red: dict, match) -> float:
    """Device time of the ops whose name ``match(name)`` accepts, averaged
    over the devices."""
    devs = red["devices"]
    return sum(d for dev in devs for n, d in dev["by_op"].items()
               if match(n)) / max(len(devs), 1)


def module_time(red: dict, match) -> tuple[int, float]:
    """(runs, ns) of the jitted programs ``match`` accepts, averaged over
    the devices."""
    devs = red["devices"]
    n = sum(c for dev in devs for k, (c, _) in dev["by_module"].items()
            if match(k))
    t = sum(d for dev in devs for k, (_, d) in dev["by_module"].items()
            if match(k))
    return n // max(len(devs), 1), t / max(len(devs), 1)


def busy_s(red: dict) -> float:
    devs = red["devices"]
    return sum(d["busy_ns"] for d in devs) / max(len(devs), 1) / 1e9


def top_ops(red: dict, n: int = 10) -> list:
    """The n device ops (by name and result type) that took most time,
    containers left out."""
    dev = red["devices"][0]
    ops = [(k, v) for k, v in dev["by_op"].items()
           if not k.startswith(CONTAINERS)]
    return [[k, v / 1e9] for k, v in
            sorted(ops, key=lambda kv: -kv[1])[:n]]


def pool_share(red: dict, pool_dims: str) -> float:
    """Share of device time (containers left out) in ops outside every
    kernel whose result has the KV pool's ``<blocks>,<block size>`` dims:
    the pool's copies, slices and updates."""
    dev = red["devices"][0]
    total = sum(v for k, v in dev["by_op"].items()
                if not k.startswith(CONTAINERS))
    pool = sum(v for k, v in dev["by_op"].items()
               if f"[{pool_dims}," in k or f",{pool_dims}," in k)
    return pool / total if total else 0.0


def idle_gaps(red: dict, n: int = 10) -> list:
    """The longest gaps between device ops, each named by the bench span
    the host was in at the gap's middle ("host: other" outside them)."""
    dev = red["devices"][0]
    spans = red["spans"]
    out = []
    for s, e in sorted(dev["gaps"], key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        name = next((nm for nm, a, b in spans if a <= mid < b),
                    "host: other")
        out.append([name, (e - s) / 1e9])
    return out
