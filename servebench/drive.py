"""The timed window: the harness drives ``repro.serving.Engine`` through its
public ``submit()`` and ``step()`` for a fixed number of seconds.

A closed loop is opened in set-up (``Driver.fill``): every client's first
request, which holds the output it has already emitted in its prompt, is
submitted and prefilled, so the window opens with every slot decoding a
context of the size a running deployment holds.

Every call into the engine runs inside a ``jax.profiler.TraceAnnotation``
(``sb.submit``, ``sb.step``) and, when the loop has nothing to do, the
wait runs inside ``sb.wait``, so a device trace can name what the host was
doing in each idle gap. A token is stamped when the step that produced it
returns (the step has synced with the device by then).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import numpy as np

from servebench.traffic import Plan, Planned


@dataclass
class Rec:
    """One request as the harness saw it."""
    index: int
    prompt: np.ndarray
    max_new: int
    due_t: float                       # when it was due (host clock)
    client: int = -1                   # closed loop: the client that sent it
    submit_t: Optional[float] = None
    token_t: List[float] = field(default_factory=list)
    done_t: Optional[float] = None
    req: object = None                 # the engine's Request
    planned: Optional[Planned] = None

    @property
    def tokens(self) -> List[int]:
        return list(self.req.out_tokens) if self.req is not None else []

    @property
    def finish_reason(self) -> str:
        return self.req.finish_reason if self.req is not None else ""


@dataclass
class StepRec:
    t0: float
    t1: float
    kind: str            # prefill | decode | idle
    rows: int            # requests that got a token from this step
    context: int         # decode: tokens attended over all rows
    pages_used: int = 0  # paged KV pool: pages held after the step


@dataclass
class Window:
    t0: float
    t1: float
    recs: List[Rec]
    steps: List[StepRec]
    first_traced_step: Optional[int] = None   # index of the first step
    #                                           the device trace holds

    def finished(self) -> List[Rec]:
        return [r for r in self.recs
                if r.done_t is not None and r.done_t <= self.t1]

    def running(self) -> List[Rec]:
        """Submitted in the window and not finished by its close."""
        return [r for r in self.recs if r.submit_t is not None
                and (r.done_t is None or r.done_t > self.t1)]

    def lateness_s(self) -> List[float]:
        return [r.submit_t - r.due_t for r in self.recs
                if r.submit_t is not None]


def _make_request(p: Planned):
    from repro.serving import Request
    return Request(uid=p.index, prompt=p.prompt, max_new=p.max_new)


class Driver:
    """One traffic plan over one engine: ``fill()`` in set-up, then
    ``window()``. ``tick(now, n_steps)`` is called before every window step
    (the profiler's start and stop)."""

    def __init__(self, eng, plan: Plan,
                 clock: Callable[[], float] = time.perf_counter):
        self.eng, self.plan, self.clock = eng, plan, clock
        self.recs: List[Rec] = []
        self.steps: List[StepRec] = []
        self.active: List[Rec] = []
        self.queue = list(plan.queue)
        self.paged = bool(eng.plan.has_paged)

    def _submit(self, rec: Rec) -> None:
        rec.req = _make_request(rec.planned)
        with jax.profiler.TraceAnnotation("sb.submit"):
            self.eng.submit(rec.req)
        rec.submit_t = self.clock()
        self.active.append(rec)

    def _new_rec(self, p: Planned, due: float, client: int = -1) -> Rec:
        r = Rec(p.index, p.prompt, p.max_new, due, client, planned=p)
        self.recs.append(r)
        return r

    def _step(self) -> None:
        eng, clock = self.eng, self.clock
        n_dec, n_pre = eng.stats["decode_steps"], eng.stats["prefill_steps"]
        s0 = clock()
        with jax.profiler.TraceAnnotation("sb.step"):
            eng.step()
        s1 = clock()
        kind = ("decode" if eng.stats["decode_steps"] > n_dec else
                "prefill" if eng.stats["prefill_steps"] > n_pre else "idle")
        rows = ctx = 0
        done: List[Rec] = []
        for r in self.active:
            n = len(r.req.out_tokens)
            if n > len(r.token_t):
                r.token_t.extend([s1] * (n - len(r.token_t)))
                rows += 1
                ctx += len(r.prompt) + n - 1
            if r.req.done:
                r.done_t = s1
                done.append(r)
        used = eng.sched.alloc.used_pages if self.paged else 0
        self.steps.append(StepRec(s0, s1, kind, rows, ctx, used))
        for r in done:
            self.active.remove(r)
            if self.plan.loop == "closed" and self.queue:
                self._submit(self._new_rec(self.queue.pop(0), clock(),
                                           r.client))

    def fill(self) -> float:
        """Closed loop: submit every client's first request and step until
        each has been prefilled (has its first token). Returns the seconds
        it took; the steps are not the window's."""
        t = self.clock()
        if self.plan.loop == "closed":
            for c, p in enumerate(self.plan.first):
                self._submit(self._new_rec(p, t, c))
            firsts = list(self.recs)
            while any(not r.token_t for r in firsts) and \
                    self.eng.sched.has_work:
                self._step()
        self.steps = []
        return self.clock() - t

    def window(self, seconds: float,
               tick: Optional[Callable[[float, int], None]] = None
               ) -> Window:
        eng, clock = self.eng, self.clock
        t0 = clock()
        t1 = t0 + seconds
        pending: List[Rec] = []        # open loop: due, not yet submitted
        if self.plan.loop == "open":
            pending = [self._new_rec(p, t0 + p.due) for p in self.queue
                       if p.due < seconds]
            self.queue = []
        while True:
            now = clock()
            if now >= t1:
                break
            while pending and pending[0].due_t <= now:
                self._submit(pending.pop(0))
            if tick is not None:
                tick(now, len(self.steps))
            if not eng.sched.has_work:
                if not pending:
                    break              # closed loop ran dry
                with jax.profiler.TraceAnnotation("sb.wait"):
                    time.sleep(max(0.0, min(pending[0].due_t, t1) - clock()))
                continue
            self._step()
        return Window(t0, t1, self.recs, self.steps)


def run_window(eng, plan: Plan, seconds: float,
               tick: Optional[Callable[[float, int], None]] = None,
               clock: Callable[[], float] = time.perf_counter) -> Window:
    """Fill, then drive ``eng`` with ``plan`` for ``seconds``."""
    d = Driver(eng, plan, clock)
    d.fill()
    return d.window(seconds, tick)
