"""The load loops: what the measured window drives, and what it records.

Every request is timed from its due time to the moment its answer is on
the host: the end of the `submit` that served it from the cache, or of the
`pump` that harvested it. A closed-loop client is due again the moment it
has its answer; an open-loop arrival is due at its time on the schedule,
however late the loop comes to send it. So a stalled pump shows in every
request that waited behind it.

The server is driven only through its public calls: `submit`, `pump`, and
the pools' `steps` (read, never written). Each call is
wrapped in a `jax.profiler.TraceAnnotation`, so a traced run can tell what
the host was doing while the device sat idle.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Callable, Dict, List, Optional

import jax

now = time.perf_counter


class CompileClock:
    """Seconds jax spends compiling executables, and how many it compiled
    or loaded from the persistent cache (copied from `chip_smoke.py`)."""

    def __init__(self):
        self.secs = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.secs, self.compiles, self.cache_hits

    def since(self, mark) -> dict:
        s, c, h = mark
        return {"seconds": self.secs - s, "compiles": self.compiles - c,
                "cache_hits": self.cache_hits - h}


@dataclasses.dataclass
class Request:
    program: str
    source: int
    due: float
    submit: float = 0.0
    rid: Optional[int] = None
    done: Optional[float] = None       # answer on the host
    from_cache: bool = False
    iterations: int = 0
    completion: object = None          # the server's Completion

    @property
    def latency(self) -> Optional[float]:
        return None if self.done is None else self.done - self.due


@dataclasses.dataclass
class Pump:
    start: float
    end: float
    steps: int          # pool steps this pump ran


class Client:
    """The load generator's side of one `GraphServer`: submits, pumps and
    records every request."""

    def __init__(self, srv):
        self.srv = srv
        self.requests: List[Request] = []
        self.pumps: List[Pump] = []
        self.by_rid: Dict[int, Request] = {}
        self.outstanding = 0

    def _pools(self):
        for grp in self.srv.pool_groups.values():
            yield from grp

    def submit(self, program: str, source: int, due: float) -> Request:
        req = Request(program, int(source), due)
        self.requests.append(req)
        n0 = len(self.srv.completions)
        with jax.profiler.TraceAnnotation("bench.submit"):
            req.submit = now()
            rid = self.srv.submit(program, int(source))
        if rid is None:          # refused at the queue: never answered
            return req
        req.rid = rid
        self.by_rid[rid] = req
        self.outstanding += 1
        self._finish(self.srv.completions[n0:], now())
        return req

    def pump(self) -> List[Request]:
        """One `pump()`; returns the requests it answered."""
        steps0 = sum(p.steps for p in self._pools())
        t0 = now()
        with jax.profiler.TraceAnnotation("bench.pump"):
            comps = self.srv.pump()
        t1 = now()
        self.pumps.append(Pump(t0, t1, sum(p.steps for p in self._pools())
                               - steps0))
        return self._finish(comps, t1)

    def _finish(self, comps, t: float) -> List[Request]:
        out = []
        for c in comps:
            req = self.by_rid.get(c.rid)
            if req is None or req.done is not None:
                continue
            req.done = t
            req.from_cache = bool(c.from_cache)
            req.iterations = int(c.iterations)
            req.completion = c
            self.outstanding -= 1
            out.append(req)
        return out

    def drain(self, until: float) -> None:
        while self.outstanding and now() < until:
            self.pump()


def warm_up(drv: Client, sources: Dict[str, List[int]], until: float) -> None:
    """Fill every lane of every pool once and run them dry: compiles (or
    loads) admission, the step and each lane's harvest, at the cell's lane
    counts and no others."""
    t = now()
    for prog, srcs in sources.items():
        for s in srcs:
            drv.submit(prog, s, t)
    drv.drain(until)
    if drv.outstanding:
        raise RuntimeError("warm-up queries did not finish")
    drv.srv.cache.clear()
    drv.requests.clear()
    drv.pumps.clear()
    drv.by_rid.clear()


def closed_loop(drv: Client, queues: Dict[str, List[int]], outstanding: int,
                start: float, seconds: float, on_close: Callable[[], None],
                drain_until: float) -> None:
    """`outstanding` clients per program; each sends its next source as
    soon as it has its answer, until the window closes. A queue that runs
    out starts over (only a small test graph has so few keys)."""
    end = start + seconds
    nxt = {p: itertools.cycle(q) for p, q in queues.items()}
    for p in queues:
        for _ in range(outstanding):
            _resend(drv, drv.submit(p, next(nxt[p]), start), nxt, end)
    closed = False
    while drv.outstanding and now() < drain_until:
        for req in drv.pump():
            _resend(drv, req, nxt, end)
        if not closed and now() >= end:
            closed = True
            on_close()
    if not closed:
        on_close()


def _resend(drv: Client, req: Request, nxt, end: float) -> None:
    """A client whose answer is in sends again (a cache hit answers at
    once, so it may send several in a row)."""
    while req.done is not None and req.done < end:
        req = drv.submit(req.program, next(nxt[req.program]), req.done)


def open_loop(drv: Client, arrivals, start: float, seconds: float,
              on_close: Callable[[], None], drain_until: float) -> None:
    """Send each arrival when it is due, pump while anything is in flight,
    and drain once the last has been sent."""
    end = start + seconds
    i = 0
    closed = False
    while now() < drain_until:
        t = now()
        while i < len(arrivals) and start + arrivals[i].t <= t:
            a = arrivals[i]
            drv.submit(a.program, a.source, start + a.t)
            i += 1
        if not closed and t >= end:
            closed = True
            on_close()
        if drv.outstanding:
            drv.pump()
        elif closed and i == len(arrivals):
            break
        else:
            due = start + arrivals[i].t if i < len(arrivals) else end
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(due - now(), 0.002)))
    if not closed:
        on_close()
