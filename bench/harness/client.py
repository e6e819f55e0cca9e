"""The client side of the engine: submits requests, steps the engine,
and times every token from outside.

A closed loop: each client's next request is due the moment its last one
finishes.  Before every ``Engine.step()`` the loop submits every request
that is due.  A token's time is the end of the step after which it
first appears in its slot's output.

Warm-in: the clients send their first requests spread evenly over
``warm_s``, so that their requests are out of step, and the window opens
then, on a full batch.  When its seconds are up nothing more starts in
it; the step that was running then counts whole, and the window closes
at that step's end, so that it holds whole steps only and all of their
time.  After the window the loop keeps stepping, for
``drain_s`` at most, until every request due inside it has its first
token and as many requests have finished as the reference samples; a
request due in the window with no first token by then counts as failed.
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Callable, Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation as span

from harness.traffic import Item

clock = time.perf_counter


@dataclasses.dataclass
class Req:
    rid: int
    due: float
    prompt_len: int
    max_new: int
    submit: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    client: int = -1
    outcome: str = ""


@dataclasses.dataclass
class Step:
    start: float
    end: float
    decoded: int
    contexts: List[int]        # context length of each decoded slot
    blocks: List[tuple]        # (start, width) of each prefill block
    pages: int = 0             # KV pages owned by live slots after it


@dataclasses.dataclass
class Record:
    reqs: Dict[int, Req]
    steps: List[Step]
    t0: float = 0.0            # window opens
    t1: float = 0.0            # window closes
    end: float = 0.0           # loop ends (after the drain)
    lateness: List[float] = dataclasses.field(default_factory=list)

    def due_in_window(self) -> List[Req]:
        return [r for r in self.reqs.values() if self.t0 <= r.due < self.t1]

    def in_window(self) -> List[Req]:
        """Requests in flight at some time of the window: due before it
        closes, and not ended before it opened."""
        return [r for r in self.reqs.values() if r.due < self.t1
                and not (r.outcome and r.times and r.times[-1] < self.t0)]

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.t0 <= s.end <= self.t1]


def _slot_view(engine):
    """rid → (prefill progress, tokens out, decoding?, write position)."""
    view = {}
    for s in engine.sched.slots:
        if s is not None:
            view[s.req.rid] = (s.prefill_progress, len(s.out), s.prefilled,
                               s.write_pos)
    return view


class Loop:
    """Drives one engine through one run of a traffic mix."""

    def __init__(self, engine, mix: Dict, make_request: Callable,
                 hooks: Optional[Dict[str, Callable]] = None,
                 tracer=None):
        self.engine = engine
        self.mix = mix
        self.make_request = make_request
        self.hooks = hooks or {}
        self.tracer = tracer
        self.rec = Record(reqs={}, steps=[])
        self._next_rid = 0

    def _submit(self, item: Item, due: float, client: int):
        rid = self._next_rid
        self._next_rid += 1
        now = clock()
        self.rec.reqs[rid] = Req(rid, due, len(item.prompt), item.max_new,
                                 submit=now, client=client)
        self.rec.lateness.append(now - due)
        self.engine.submit(self.make_request(rid, item))
        return rid

    def _step(self) -> List[int]:
        """One engine step; returns the rids that ended in it."""
        eng = self.engine
        before = _slot_view(eng)
        contexts = [wp + 1 for (_, _, dec, wp) in before.values() if dec]
        t = clock()
        with span("bench_step"):
            hook = self.hooks.get("step")
            info = hook(eng) if hook else eng.step()
        end = clock()
        after = _slot_view(eng)
        blocks, ended = [], []
        for rid in set(before) | set(after):
            p0, n0 = before.get(rid, (0, 0, False, 0))[:2]
            if rid in after:
                p1, n1 = after[rid][:2]
            else:
                ended.append(rid)
                res = eng.results.get(rid)
                p1 = self.rec.reqs[rid].prompt_len
                n1 = len(res.tokens) if res is not None else n0
                self.rec.reqs[rid].outcome = (res.outcome.value if res
                                              else "lost")
            if p1 > p0:
                blocks.append((p0, p1 - p0))
            self.rec.reqs[rid].times.extend([end] * max(n1 - n0, 0))
        if info["decoded"] != len(contexts):
            contexts = contexts[:info["decoded"]]
        self.rec.steps.append(Step(t, end, info["decoded"], contexts,
                                   blocks, eng.pool.used_pages))
        return ended

    def _pending(self) -> bool:
        """A request due in the window still waits for its first token,
        or fewer requests have finished than the reference samples."""
        done = sum(r.outcome == "finished" for r in self.rec.reqs.values())
        return (done < self.mix["reference_requests"]
                or any(not r.times and not r.outcome
                       for r in self.rec.due_in_window()))

    def run(self, items: List[Item], seconds: float) -> Record:
        try:
            self._run(items, seconds)
        finally:
            if self.tracer is not None:
                self.tracer.stop()
        self.rec.end = clock()
        for r in self.rec.reqs.values():
            if not r.outcome and r.rid in self.engine.results:
                r.outcome = self.engine.results[r.rid].outcome.value
        return self.rec

    def _run(self, items: List[Item], seconds: float):
        mix, start = self.mix, clock()
        feed = iter(items)
        due = []            # heap of (due time, order, item, client)
        # client c sends its first request at c/clients of the warm-in
        n = mix["clients"]
        for c in range(n):
            heapq.heappush(due, (start + c * mix["warm_s"] / n, c,
                                 self._next(feed), c))
        order = len(due)
        opened = closed = False
        while True:
            now = clock()
            if self.tracer is not None:
                self.tracer.tick(now)
            if not opened and now >= start + mix["warm_s"]:
                if self.tracer is not None:
                    self.tracer.start()
                    now = clock()
                self.rec.t0, self.rec.t1 = now, now + seconds
                opened = True
            if opened and not closed and now >= self.rec.t1:
                if self.rec.steps:
                    self.rec.t1 = max(self.rec.t1, self.rec.steps[-1].end)
                closed = True
            while due and due[0][0] <= now:
                at, _, item, c = heapq.heappop(due)
                self._submit(item, at, c)
            if opened and now >= self.rec.t1 and (
                    not self._pending()
                    or now >= self.rec.t1 + mix["drain_s"]):
                return
            if self.engine.sched.has_work():
                ended = self._step()
            elif due:
                with span("bench_wait"):
                    time.sleep(min(max(due[0][0] - clock(), 0.0), 0.002))
                continue
            else:
                raise RuntimeError("no request is due or in flight")
            end = self.rec.steps[-1].end
            for rid in ended:
                order += 1
                heapq.heappush(due, (end, order, self._next(feed),
                                     self.rec.reqs[rid].client))

    @staticmethod
    def _next(feed):
        item = next(feed, None)
        if item is None:
            raise RuntimeError("the closed loop ran out of requests; raise "
                               "per_client")
        return item


def percentile(values, q: float) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts(rec: Record) -> List[float]:
    return [r.times[0] - r.due for r in rec.due_in_window() if r.times]


def token_gaps(rec: Record) -> List[float]:
    out = []
    for r in rec.reqs.values():
        t = np.asarray(r.times)
        if t.size < 2:
            continue
        g = np.diff(t)
        keep = (t[1:] >= rec.t0) & (t[1:] <= rec.t1)
        out.extend(g[keep].tolist())
    return out


def window_tokens(rec: Record) -> int:
    return sum(int(np.sum((np.asarray(r.times) >= rec.t0)
                          & (np.asarray(r.times) <= rec.t1)))
               for r in rec.reqs.values())
