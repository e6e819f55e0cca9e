"""The measured window holds whole steps: when its seconds run out, the
step then running counts whole, tokens and time, and the window closes at
its end; a window whose last step ended in time closes on the second."""
import types

import numpy as np
import pytest

from harness import client, traffic


class _Slot:
    def __init__(self, req):
        self.req = req
        self.prefill_progress = self.write_pos = len(req.prompt)
        self.prefilled = True
        self.out = []


class _Engine:
    """Decodes one token per live slot per step, each step ``step_s`` on
    the fake clock ``now``."""

    def __init__(self, now, step_s, slots):
        self.now, self.step_s = now, step_s
        self.queue = []
        self.sched = types.SimpleNamespace(
            slots=[None] * slots,
            has_work=lambda: bool(self.queue) or any(self.sched.slots))
        self.pool = types.SimpleNamespace(used_pages=0)
        self.results = {}

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        slots = self.sched.slots
        for i, s in enumerate(slots):
            if s is None and self.queue:
                slots[i] = _Slot(self.queue.pop(0))
        self.now[0] += self.step_s
        live = [s for s in slots if s is not None]
        for s in live:
            s.out.append(0)
            s.write_pos += 1
        return {"decoded": len(live)}


@pytest.mark.parametrize("step_s,closes_at", [(0.3, 1.2), (0.25, 1.0)])
def test_window_counts_whole_steps(monkeypatch, step_s, closes_at):
    now = [0.0]
    monkeypatch.setattr(client, "clock", lambda: now[0])
    mix = {"clients": 2, "warm_s": 0.0, "drain_s": 0.0,
           "reference_requests": 0}
    items = [traffic.Item(np.zeros((16,), np.int32), 100)
             for _ in range(2)]
    loop = client.Loop(_Engine(now, step_s, slots=2), mix,
                       lambda rid, item: types.SimpleNamespace(
                           rid=rid, prompt=item.prompt))
    rec = loop.run(items, 1.0)
    assert rec.t0 == 0.0 and rec.t1 == pytest.approx(closes_at)
    assert rec.t1 == rec.steps[-1].end
    assert len(rec.window_steps()) == 4
    assert client.window_tokens(rec) == 8
