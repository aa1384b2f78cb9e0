"""Traffic: one general open-loop and one closed-loop generator of
``AsyncServeEngine``, both read from a mix's parameters.

The engine is cooperative and single-threaded: ``pump()`` serves one
resident launch and returns when it has ended.  So the generators submit what
is due, pump, and take the responses as each pump returns.  Every request
and launch of the window is kept as a plain record; the answers are kept
as copies of the output rows, and each response (which holds every DRAM
array of its request) is dropped as soon as it is read.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from jax.profiler import TraceAnnotation


def poisson_offsets(n: int, rate: float, rng: np.random.Generator
                    ) -> np.ndarray:
    """Arrival offsets (seconds from the window's start) of ``n`` requests
    at ``rate`` per second.  The gaps are the ``n`` quantiles of the
    exponential distribution of mean ``1/rate`` (the inter-arrival law of a
    Poisson process), scaled to that mean exactly, in an order drawn from
    the seed: every seed offers the same set of gaps, only their order
    (and so the bursts) differs.  The first request is due at 0."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= (n / rate) / gaps.sum()
    gaps = rng.permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def percentile(values, q: float) -> float:
    """``q``-th percentile with linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, float), q))


@dataclass
class Req:
    index: int                      # request index, fixes its data
    tenant: str
    due: float                      # scheduled (open) or issued (closed)
    client: int = -1                # closed loop: the client that sent it
    submit_t: Optional[float] = None
    admit_t: Optional[float] = None
    done_t: Optional[float] = None
    status: str = "unanswered"      # ok | shed | failed | unanswered
    execution: Optional[str] = None  # the report's execution mode
    answer: Optional[np.ndarray] = None
    correct: bool = False           # set by the check after the window


@dataclass
class Launch:
    size: int                       # slots launched (the bucket)
    served: int                     # requests in it
    wall_s: float                   # engine.launch_walls: run_batch wall
    start: float = 0.0              # pump() called (window clock)
    pump_s: float = 0.0             # pump() wall, image work included


@dataclass
class Window:
    t0: float
    seconds: float
    requests: list = field(default_factory=list)
    launches: list = field(default_factory=list)
    traced: Optional[tuple] = None  # (first, last) launch index traced


class Traffic:
    """Runs one window on ``engine`` (whose clock is ``time.perf_counter``);
    ``make_request(index)`` gives the ``(arrays, scalars)`` of a request,
    ``rows`` how many answers it has, ``output`` the DRAM array that holds
    them.  ``tracer`` (optional, a :class:`bench.trace.Tracer`) is started
    before the window's launch ``tracer.first`` and stopped after launch
    ``tracer.last``."""

    def __init__(self, engine, make_request: Callable, rows: int,
                 output: str, mix: dict, *, tracer=None):
        self.engine = engine
        self.make_request = make_request
        self.rows = rows
        self.output = output
        self.mix = mix
        self.clock = time.perf_counter
        self.tracer = tracer
        self.tenants = [f"tenant-{i}" for i in range(int(mix["tenants"]))]
        self._by_id: dict[int, Req] = {}
        self.w: Optional[Window] = None

    # ----------------------------------------------------------- plumbing
    def _submit(self, req: Req) -> None:
        from repro.serve.async_engine import AsyncRequest
        arrays, scalars = self.make_request(req.index)
        with TraceAnnotation("bench.submit"):
            ar = self.engine.submit(AsyncRequest(
                params=scalars, dram_init=arrays, tenant=req.tenant))
        req.submit_t = ar.submit_t
        self._by_id[ar.id] = req
        self.w.requests.append(req)
        self._collect()

    def _collect(self) -> list[Req]:
        """Read every response the engine has resolved, then drop it."""
        out = []
        with TraceAnnotation("bench.collect"):
            for resp in self.engine.done:
                req = self._by_id.pop(resp.request.id)
                req.admit_t = resp.request.admit_t
                req.done_t = resp.request.done_t
                req.status = resp.status
                if resp.report is not None:
                    req.execution = resp.report.execution
                if resp.ok:
                    req.answer = np.array(
                        resp.dram[self.output][:self.rows])
                out.append(req)
            self.engine.done.clear()
        return out

    def _pump(self) -> list[Req]:
        k = len(self.w.launches)
        tr = self.tracer
        if tr is not None and k == tr.first:
            tr.start()
        n_walls = len(self.engine.launch_walls)
        t = self.clock()
        with TraceAnnotation("bench.pump"):
            self.engine.pump()
        pump_s = self.clock() - t
        done = self._collect()
        if len(self.engine.launch_walls) > n_walls:
            _mode, size, wall = self.engine.launch_walls[-1]
            self.w.launches.append(Launch(size, len(done), wall,
                                          t - self.w.t0, pump_s))
            if tr is not None and k == tr.last:
                tr.stop()
                self.w.traced = (tr.first, tr.last)
        return done

    # ---------------------------------------------------------- generators
    def run(self, seconds: float, rng: np.random.Generator) -> Window:
        kind = self.mix["kind"]
        if kind == "open":
            return self._open(seconds, rng)
        if kind == "closed":
            return self._closed(seconds)
        raise ValueError(f"unknown traffic kind {kind!r}")

    def _open(self, seconds: float, rng: np.random.Generator) -> Window:
        """Poisson arrivals at the mix's ``rate_rps`` from alternating
        tenants; latency runs from each request's scheduled time."""
        rate = float(self.mix["rate_rps"])
        n = max(1, int(round(rate * seconds)))
        offsets = poisson_offsets(n, rate, rng)
        self.w = Window(t0=self.clock(), seconds=seconds)
        t0 = self.w.t0
        i = 0
        while True:
            now = self.clock()
            while i < n and t0 + offsets[i] <= now:
                self._submit(Req(i, self.tenants[i % len(self.tenants)],
                                 due=t0 + offsets[i]))
                i += 1
            if self.engine.queue_depth:
                self._pump()
            elif i < n:
                with TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, t0 + offsets[i] - self.clock()))
            elif self.engine.pending:
                self._pump()
            else:
                break
        return self.w

    def _closed(self, seconds: float) -> Window:
        """``clients`` clients, all starting at the window's start, each
        sending its next request the moment its reply arrives, until the
        window ends; then the requests in flight drain.  Latency runs
        from when the client sent the request."""
        clients = int(self.mix["clients"])
        self.w = Window(t0=self.clock(), seconds=seconds)
        end = self.w.t0 + seconds
        nxt = 0

        def send(client: int) -> None:
            nonlocal nxt
            self._submit(Req(nxt, self.tenants[client % len(self.tenants)],
                             due=self.clock(), client=client))
            nxt += 1

        for c in range(clients):
            send(c)
        while self.engine.pending:
            for req in self._pump():
                if self.clock() < end:
                    send(req.client)
        return self.w
