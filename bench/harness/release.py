"""Wall-clock arrivals around the serving engine.

``repro.serving.ServingEngine`` schedules arrivals in engine steps and
serves a fixed list to completion. ``WallClockEngine`` turns that into a
timed window under steady load without touching the program: it
overrides the engine's per-step arrival stamping (``_stamp_arrivals``, a
private method) so that

- every request starts unreleased (parked behind a sentinel that keeps
  the engine loop alive between arrivals);
- the first call starts the stream: the warm-up requests fall due first,
  and the measured window opens when the warm-up has run (the set-up ends
  at the first release, the window later);
- a request is released when it falls due, with ``arrival`` set to the
  current step and ``arrival_t`` to its DUE time, so TTFT counts any
  wait a slow host loop imposed;
- arrivals go on after the window closes, until every request due in it
  has finished (or ``drain_limit_s`` has passed); then the run stops with
  ``WindowDone``, abandoning the load-only requests still in flight.

It also records, with the host clock, each fused dispatch (plan and
snapshot: ``_dispatch_fused``; the enqueue inside it, the call of
``StepExecutor.step_fused_paged``, apart) and each readback, and can
bracket a stretch of dispatches with the JAX profiler.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import jax

from repro.serving import ServingEngine
from repro.serving.request import Request

PARKED = 1e9          # arrival step of a request not yet released


class WindowDone(Exception):
    """Every measured request has finished, or the drain limit passed."""


@dataclasses.dataclass
class Dispatch:
    t0: float
    t1: float
    padded: int           # rows the step computed (granule-rounded)
    live: int             # real rows
    backend: Optional[str]
    occupied: int         # lanes holding a request
    enqueue_s: float      # of t1 - t0, inside StepExecutor.step_fused_paged
    ctx: list             # keys each live row attends (position + 1)
    logit_rows: int       # live rows whose logits are used


@dataclasses.dataclass
class TracePlan:
    """Profile the dispatches of [start_s, start_s + seconds) into the
    window; ``directory`` receives the profiler's output."""
    start_s: float
    seconds: float
    directory: str


class WallClockEngine(ServingEngine):
    """ServingEngine whose requests are released on the wall clock."""

    def prime(self, requests: list[Request], *, due_s: list,
              measured: list, seconds: float, drain_limit_s: float,
              trace: Optional[TracePlan] = None, compiles=None) -> None:
        """Set the run's plan: requests[i] falls due ``due_s[i]`` seconds
        after the window opens (negative: warm-up) and is timed iff
        ``measured[i]``; a window of ``seconds``; ``compiles`` (a
        CompileLog) is read as the stream starts and as the run stops."""
        self.seconds = seconds
        self.drain_limit_s = drain_limit_s
        self._compiles = compiles
        self.compiles_window = [0, 0]
        self.trace = trace
        self._reqs = requests
        self._due_s = due_s
        self._measured = [r for r, m in zip(requests, measured) if m]
        self._timed = {r.rid for r in self._measured}
        for r in requests:
            r.arrival = PARKED
        self._next = 0
        self._sentinel = Request(rid=-1, prompt=[0], max_new=1,
                                 arrival=2 * PARKED)
        self.t_start: Optional[float] = None
        self.t0: Optional[float] = None
        self.t_end = 0.0
        self.t_stop = 0.0
        self.released: list[Request] = []     # measured, released
        self.load_released = 0
        self.lateness_s: list[float] = []
        self.dispatches: list[Dispatch] = []
        self.emits: list[tuple[float, int]] = []
        self.trace_t = [None, None]      # host clock of start/stop
        self.trace_dispatches = [None, None]
        self._enqueue_s = 0.0
        if not hasattr(self.executor, "timed_step"):
            step = self.executor.step_fused_paged

            def timed_step(*a, **k):
                t = time.perf_counter()
                try:
                    return step(*a, **k)
                finally:
                    self._enqueue_s += time.perf_counter() - t
            self.executor.step_fused_paged = self.executor.timed_step = \
                timed_step

    # ------------------------------------------------------- arrivals

    def _release(self, r: Request, step: int, due_t: float,
                 now: float, timed: bool) -> None:
        r.arrival = step
        r.arrival_t = due_t
        pending = self.scheduler.pending
        pending.pop()                      # the sentinel stays last
        pending.append(r)
        pending.append(self._sentinel)
        if timed:
            self.released.append(r)
            self.lateness_s.append(now - due_t)
        else:
            self.load_released += 1

    def _stamp_arrivals(self, requests, step: int) -> None:
        now = time.perf_counter()
        if self.t_start is None:
            self.t_start = now
            self.t0 = now - self._due_s[0]
            self.t_end = self.t0 + self.seconds
            self.scheduler.pending = deque([self._sentinel])
            self.compiles_window = [self._n_compiles()] * 2
        if now >= self.t_end and all(r.done for r in self._measured) or \
                now >= self.t_end + self.drain_limit_s:
            self.t_stop = now
            self.compiles_window[1] = self._n_compiles()
            raise WindowDone
        while self._next < len(self._reqs) and \
                self.t0 + self._due_s[self._next] <= now:
            r = self._reqs[self._next]
            self._release(r, step, self.t0 + self._due_s[self._next], now,
                          timed=r.rid in self._timed)
            self._next += 1

    def _n_compiles(self) -> int:
        return self._compiles.n if self._compiles is not None else 0

    # ------------------------------------------------ dispatch / readback

    def _trace_edge(self, now: float) -> None:
        tp = self.trace
        if self.t0 is None or tp is None:
            return
        i = 0 if self.trace_t[0] is None else 1
        if i == 1 and self.trace_t[1] is not None:
            return
        at = self.t0 + tp.start_s + (tp.seconds if i else 0.0)
        if now < at:
            return
        # the device finishes what is queued first, so the profile holds
        # whole steps: exactly the dispatches made while it was on
        jax.block_until_ready(self.kv.cache)
        if i == 0:
            jax.profiler.start_trace(tp.directory)
            self.trace_t[0] = time.perf_counter()
        else:
            self.trace_t[1] = time.perf_counter()
            jax.profiler.stop_trace()
        self.trace_dispatches[i] = len(self.dispatches)

    @property
    def kv_dims(self) -> str:
        """``<pool blocks>,<block size>`` of the paged pool's leaves (the
        engine's default pool plus its trash block)."""
        per_slot = -(-self.max_len // self.block_size)
        blocks = self.num_blocks or self.max_slots * per_slot
        return f"{blocks + 1},{self.block_size}"

    @property
    def tracing(self) -> bool:
        return self.trace_t[0] is not None and self.trace_t[1] is None

    def _dispatch_fused(self, step: int, slot_tokens):
        self._trace_edge(time.perf_counter())
        self._enqueue_s = 0.0
        t0 = time.perf_counter()
        if self.tracing:
            with jax.profiler.TraceAnnotation("bench.plan_dispatch"):
                out = super()._dispatch_fused(step, slot_tokens)
        else:
            out = super()._dispatch_fused(step, slot_tokens)
        t1 = time.perf_counter()
        rec, _, occupied = out
        if rec is not None:
            self.dispatches.append(Dispatch(
                t0=t0, t1=t1, padded=rec.padded, live=rec.live,
                backend=rec.backend, occupied=occupied,
                enqueue_s=self._enqueue_s,
                ctx=[row.pos + 1 for row in rec.rows],
                logit_rows=sum(row.kind != "mid" for row in rec.rows)))
        return out

    def _readback_fused(self, rec, inflight) -> None:
        emitted = sum(1 for row in rec.rows
                      if row.kind != "mid" and row.valid)
        if self.tracing:
            with jax.profiler.TraceAnnotation("bench.readback"):
                super()._readback_fused(rec, inflight)
        else:
            super()._readback_fused(rec, inflight)
        self.emits.append((time.perf_counter(), emitted))

    def finish_trace(self) -> None:
        """Stop a profile still running when the run drained."""
        if self.tracing:
            jax.block_until_ready(self.kv.cache)
            self.trace_t[1] = time.perf_counter()
            jax.profiler.stop_trace()
            self.trace_dispatches[1] = len(self.dispatches)
