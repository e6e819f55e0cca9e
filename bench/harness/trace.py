"""The profiler trace of a run's traced window, and its reduction.

:class:`Tracer` traces the first ``trace_s`` seconds of the window into
a temporary directory.  :func:`load` turns the ``.xplane.pb`` into plain
event lists: the device's op events (the ``XLA Ops`` line of each TPU
plane) and the harness's own host spans (``bench_*`` annotations).  The
rest of the module works on those lists only, so a small recorded trace
checks it on the CPU.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

HOST_PREFIX = "bench_"
WINDOW_SPAN = "bench_window"
DEVICE_LINE = "XLA Ops"
# Ops that hold other ops on the same line of the trace.
CONTAINERS = ("while", "conditional", "call")
# Idle gaps shorter than this are the ordinary seams between ops.
MIN_GAP_NS = 20_000


class Tracer:
    """Traces ``seconds`` of the window, starting when it opens."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = None
        self.span = None
        self.t_start = self.t_stop = None

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)
        self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self.span.__enter__()
        self.t_start = time.perf_counter()

    def tick(self, now: float):
        if self.span is not None and now - self.t_start >= self.seconds:
            self.stop()

    def stop(self):
        import jax
        if self.span is None:
            return
        self.span.__exit__(None, None, None)
        self.span = None
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def load(self) -> Dict:
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            return load(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _meta(event) -> str:
    """The event's name with the stats that say which program op it is."""
    parts = [event.name]
    for key, val in event.stats:
        if key in ("long_name", "tf_op", "hlo_op", "hlo_module",
                   "name_stack", "source"):
            parts.append(f"{key}={val}")
    return " ".join(parts)


def load(path: str) -> Dict:
    """``{"device": [[plane, name, meta, start_ns, dur_ns], ...],
    "host": [[name, start_ns, dur_ns], ...]}`` from one xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != DEVICE_LINE:
                    continue
                for e in line.events:
                    device.append([plane.name, e.name, _meta(e),
                                   float(e.start_ns), float(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"device": device, "host": host}


def window(trace: Dict) -> Tuple[float, float]:
    spans = [h for h in trace["host"] if h[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(spans)}")
    _, start, dur = spans[0]
    return start, start + dur


def _clipped(events, w0: float, w1: float):
    for ev in events:
        s, e = max(ev[3], w0), min(ev[3] + ev[4], w1)
        if e > s:
            yield ev, s, e


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float,
                                                                 float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(trace: Dict) -> Dict[str, float]:
    """Seconds in which an op ran on the device (the union of op
    intervals, averaged over the chips traced) and the window's length."""
    w0, w1 = window(trace)
    planes = sorted({ev[0] for ev in trace["device"]})
    total = 0.0
    for plane in planes:
        evs = [ev for ev in trace["device"] if ev[0] == plane]
        total += sum(e - s for s, e in
                     _union([(s, e) for _, s, e in _clipped(evs, w0, w1)]))
    n = max(len(planes), 1)
    return {"busy_s": total / n * 1e-9, "window_s": (w1 - w0) * 1e-9}


def kernel_seconds(trace: Dict, patterns: Sequence[str]) -> float:
    """Summed device time, over the window, of the ops whose name (see
    :func:`op_name`) matches one of ``patterns`` (regular expressions,
    matched whole)."""
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    w0, w1 = window(trace)
    return sum(e - s for ev, s, e in _clipped(trace["device"], w0, w1)
               if rx.fullmatch(op_name(ev[1]))) * 1e-9


def op_name(name: str) -> str:
    """``%copy.53 = f32[...] copy(...)`` → ``copy``: the HLO op's name
    without its instruction text and number."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def top_ops(trace: Dict, n: int = 10) -> List[List]:
    """The device ops that took most time in the window, by name; the
    loops that hold other ops (``while``) are left out."""
    w0, w1 = window(trace)
    acc: Dict[str, float] = {}
    for ev, s, e in _clipped(trace["device"], w0, w1):
        name = op_name(ev[1])
        if name in CONTAINERS:
            continue
        acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
            [:n]]


def idle_gaps(trace: Dict, n: int = 10) -> List[List]:
    """Device idle time in the window by what the host was doing: the
    innermost ``bench_*`` span open at each gap's middle, or
    ``harness`` where none was."""
    w0, w1 = window(trace)
    planes = sorted({ev[0] for ev in trace["device"]})
    spans = [h for h in trace["host"] if h[0] != WINDOW_SPAN]
    acc: Dict[str, float] = {}
    for plane in planes:
        evs = [ev for ev in trace["device"] if ev[0] == plane]
        edges = [w0] + [x for s, e in _union(
            [(s, e) for _, s, e in _clipped(evs, w0, w1)]) for x in (s, e)]
        edges.append(w1)
        for s, e in zip(edges[0::2], edges[1::2]):
            if e - s < MIN_GAP_NS:
                continue
            mid = 0.5 * (s + e)
            open_ = [h for h in spans if h[1] <= mid <= h[1] + h[2]]
            name = (min(open_, key=lambda h: h[2])[0] if open_
                    else "harness")
            acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9
    scale = 1.0 / max(len(planes), 1)
    return [[k, v * scale] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def summary(trace: Optional[Dict]) -> Optional[Dict]:
    if trace is None or not trace["device"]:
        return None
    out = busy(trace)
    out["device_ops"] = top_ops(trace)
    out["idle_gaps"] = idle_gaps(trace)
    return out
