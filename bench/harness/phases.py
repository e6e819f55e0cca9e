"""The program's own phases in a profiler trace: the engine's host spans
and the program and named scope of every device op.

``harness.trace.load`` keeps the harness's ``bench_*`` spans and the op
names only.  :func:`load` reads the same ``.xplane.pb`` into the same
lists, which every function of ``harness.trace`` reads as before, and
adds:

- ``"host"``: each ``engine.*`` span of the program
  (``repro.engine.engine``) as ``[name, start_ns, dur_ns, args]``, its
  arguments (``step``, ``rid``, ...) in a dict;
- ``"device"``: each op also carries its module, the jit it ran in
  (``jit__decode_and_sample``), and its scope, the ``jax.named_scope``
  path of the op inside that jit (``while/body/closed_call/attn``), so
  an entry is ``[plane, name, meta, start_ns, dur_ns, module, scope]``.

With the program's spans among the host spans, ``trace.idle_gaps`` puts
each idle gap under the engine phase that was running.  The functions
below reduce such a trace to the engine loop's and the model's metrics;
they choose their steps by the ``engine.step`` spans inside the window,
on the profiler's clock alone.
"""
from __future__ import annotations

import re
from types import SimpleNamespace
from typing import Dict, List, Optional, Tuple

from harness import trace

PROGRAM_PREFIX = "engine."
STEP = "engine.step"
DECODE_LAUNCH = "engine.decode.launch"
PREFILL_LAUNCH = "engine.prefill.launch"
FETCH = re.compile(r"engine\.[a-z]+\.fetch")
MODULE_LINE = "XLA Modules"
# the engine's jits, by the module name XLA gives them
DECODE_MODULE = "jit__decode_and_sample"
PREFILL_MODULE = "jit_prefill_chunk_slots"
# the named scopes of the served programs (repro.models.transformer,
# repro.engine.engine), outermost first
REGIONS = ("prefill", "embed", "attn", "mlp", "head", "sample")
# the op stat that holds the op's name stack (on a TPU, in the op's
# metadata): "jit(_decode_and_sample)/while/body/closed_call/attn/...:"
SCOPE_STAT = "tf_op"


# -- reading the xplane ------------------------------------------------------

def _messages():
    """Classes for the parts of the profiler's ``XSpace`` protocol buffer
    (tsl/profiler/protobuf/xplane.proto) read here; a map field is read
    as the repeated key/value messages it is on the wire."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    f = descriptor_pb2.FieldDescriptorProto
    i64, u64, dbl, s, b, msg = (f.TYPE_INT64, f.TYPE_UINT64, f.TYPE_DOUBLE,
                                f.TYPE_STRING, f.TYPE_BYTES, f.TYPE_MESSAGE)
    one, many = f.LABEL_OPTIONAL, f.LABEL_REPEATED
    schema = {
        "XStat": [("metadata_id", 1, i64, one), ("double_value", 2, dbl, one),
                  ("uint64_value", 3, u64, one), ("int64_value", 4, i64, one),
                  ("str_value", 5, s, one), ("bytes_value", 6, b, one),
                  ("ref_value", 7, u64, one)],
        "XEvent": [("metadata_id", 1, i64, one), ("offset_ps", 2, i64, one),
                   ("duration_ps", 3, i64, one), ("stats", 4, "XStat", many)],
        "XLine": [("name", 2, s, one), ("timestamp_ns", 3, i64, one),
                  ("events", 4, "XEvent", many)],
        "XEventMetadata": [("id", 1, i64, one), ("name", 2, s, one),
                           ("stats", 5, "XStat", many)],
        "XStatMetadata": [("id", 1, i64, one), ("name", 2, s, one)],
        "EventMetadataEntry": [("key", 1, i64, one),
                               ("value", 2, "XEventMetadata", one)],
        "StatMetadataEntry": [("key", 1, i64, one),
                              ("value", 2, "XStatMetadata", one)],
        "XPlane": [("name", 2, s, one), ("lines", 3, "XLine", many),
                   ("event_metadata", 4, "EventMetadataEntry", many),
                   ("stat_metadata", 5, "StatMetadataEntry", many)],
        "XSpace": [("planes", 1, "XPlane", many)],
    }
    pkg = "bench_xplane"
    fd = descriptor_pb2.FileDescriptorProto(name=f"{pkg}.proto",
                                            package=pkg)
    for name, fields in schema.items():
        m = fd.message_type.add(name=name)
        for fname, number, typ, label in fields:
            field = m.field.add(name=fname, number=number, label=label)
            if isinstance(typ, str):
                field.type, field.type_name = msg, f".{pkg}.{typ}"
            else:
                field.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


def _value(stat, stat_names: Dict[int, str]):
    """A stat's value; a ``ref_value`` names a string kept once in the
    plane's stat metadata."""
    for field in ("str_value", "int64_value", "uint64_value",
                  "double_value"):
        if stat.HasField(field):
            return getattr(stat, field)
    if stat.HasField("ref_value"):
        return stat_names.get(stat.ref_value, "")
    return None


def _stats(stats, stat_names: Dict[int, str]) -> Dict[str, object]:
    return {stat_names.get(st.metadata_id, str(st.metadata_id)):
            _value(st, stat_names) for st in stats}


def _scope(stats: Dict[str, object]) -> str:
    """The op's named-scope path inside its jit: the name stack without
    the ``jit(...)`` frames and without the op's own name."""
    stack = stats.get(SCOPE_STAT)
    if not isinstance(stack, str):
        return ""
    parts = [p for p in stack.split("/") if p and not p.startswith("jit(")]
    return "/".join(parts[:-1])


def load(path: str) -> Dict:
    """``harness.trace.load``'s lists, with the program's spans and each
    op's module and scope (see the module's docstring)."""
    with open(path, "rb") as f:
        space = _messages().FromString(f.read())
    device: List[list] = []
    host: List[list] = []
    for plane in space.planes:
        events = {m.key: m.value for m in plane.event_metadata}
        stat_names = {m.key: m.value.name for m in plane.stat_metadata}
        if plane.name.startswith("/device:TPU:"):
            device.extend(_device_ops(plane, events, stat_names))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = events[e.metadata_id].name
                    start, dur = _times(line, e)
                    if name.startswith(trace.HOST_PREFIX):
                        host.append([name, start, dur])
                    elif name.startswith(PROGRAM_PREFIX):
                        host.append([name, start, dur,
                                     _stats(e.stats, stat_names)])
    return {"device": device, "host": host}


def _times(line, e) -> Tuple[float, float]:
    """Start and length in whole nanoseconds, as ``ProfileData`` (and so
    ``trace.load``) gives them."""
    return (float(line.timestamp_ns + e.offset_ps // 1000),
            float(e.duration_ps // 1000))


def _device_ops(plane, events, stat_names) -> List[list]:
    """The plane's ``XLA Ops`` events as ``trace.load`` lists them, each
    with its module and scope.  An op's metadata names its program
    (``program_id``), which the ``XLA Modules`` line names as
    ``jit__decode_and_sample(<program_id>)``."""
    programs = {}
    for line in plane.lines:
        if line.name == MODULE_LINE:
            for e in line.events:
                name, _, pid = events[e.metadata_id].name.rstrip(
                    ")").partition("(")
                programs[pid] = name
    where: Dict[int, Tuple[str, str]] = {}    # metadata id → module, scope
    out = []
    for line in plane.lines:
        if line.name != trace.DEVICE_LINE:
            continue
        for e in line.events:
            md = events[e.metadata_id]
            if e.metadata_id not in where:
                st = _stats(md.stats, stat_names)
                where[e.metadata_id] = (
                    programs.get(str(st.get("program_id")), ""), _scope(st))
            s, d = _times(line, e)
            meta = trace._meta(SimpleNamespace(
                name=md.name, stats=_stats(e.stats, stat_names).items()))
            out.append([plane.name, md.name, meta, s, d,
                        *where[e.metadata_id]])
    return out


# -- reductions --------------------------------------------------------------

def spans(t: Dict, name: str) -> List[list]:
    """The program's ``name`` spans that lie whole inside the window."""
    w0, w1 = trace.window(t)
    return [h for h in t["host"]
            if h[0] == name and w0 <= h[1] and h[1] + h[2] <= w1]


def step_host_ms(t: Dict) -> Optional[float]:
    """Mean host time of the window's engine steps outside the fetches:
    each ``engine.step`` span's length less the part of it that its
    ``engine.*.fetch`` spans (the host blocked on the device) cover."""
    steps = spans(t, STEP)
    if not steps:
        return None
    fetches = [(h[1], h[1] + h[2]) for h in t["host"]
               if FETCH.fullmatch(h[0])]
    total = 0.0
    for _, s0, d, _ in steps:
        inside = [(max(a, s0), min(b, s0 + d)) for a, b in fetches
                  if a < s0 + d and b > s0]
        total += d - sum(b - a for a, b in trace._union(inside))
    return total / len(steps) * 1e-6


def _seconds(t: Dict, keep) -> float:
    """Device seconds in the window in which an op that ``keep`` accepts
    ran (the union of their intervals, averaged over the chips)."""
    w0, w1 = trace.window(t)
    planes = sorted({ev[0] for ev in t["device"]})
    total = 0.0
    for plane in planes:
        iv = [(s, e) for ev, s, e in trace._clipped(t["device"], w0, w1)
              if ev[0] == plane and keep(ev)]
        total += sum(e - s for s, e in trace._union(iv))
    return total / max(len(planes), 1) * 1e-9


def region(ev) -> str:
    """The served program's named region an op ran in (``REGIONS``), or
    ``unscoped``."""
    parts = ev[6].split("/") if len(ev) > 6 else []
    return next((r for r in REGIONS if r in parts), "unscoped")


def _scoped(ev) -> bool:
    """An op that carries a module and a scope and holds no other op."""
    return len(ev) > 6 and trace.op_name(ev[1]) not in trace.CONTAINERS


def _per_launch(t: Dict, keep, launch: str) -> Optional[float]:
    """Milliseconds of device time of the ops ``keep`` accepts (their
    union in the window) per ``launch`` span in the window; None where
    the trace holds no such op or span."""
    n = len(spans(t, launch))
    if not n or not any(keep(ev) for ev in t["device"]):
        return None
    return _seconds(t, keep) / n * 1e3


def prefill_block_ms(t: Dict) -> Optional[float]:
    """Device time of the prefill program per prefill block: its ops'
    union in the window over the ``engine.prefill.launch`` spans in it."""
    return _per_launch(t, lambda ev: len(ev) > 5 and ev[5] == PREFILL_MODULE,
                       PREFILL_LAUNCH)


def decode_attn_ms(t: Dict) -> Optional[float]:
    """Device time of the decode program's ``attn`` scope (per layer: the
    norm, the q/k/v/o matmuls, the paged KV write and the attention
    kernel) per decode step: its ops' union in the window over the
    ``engine.decode.launch`` spans in it."""
    return _per_launch(t, lambda ev: (_scoped(ev) and ev[5] == DECODE_MODULE
                                      and region(ev) == "attn"),
                       DECODE_LAUNCH)


def scope_seconds(t: Dict) -> List[List]:
    """Device seconds in the window by program and region, the loops that
    hold other ops left out: ``[[module, region, seconds], ...]``."""
    keys = sorted({(ev[5], region(ev)) for ev in t["device"]
                   if _scoped(ev)})
    out = [[m, r, _seconds(t, lambda ev: _scoped(ev) and ev[5] == m
                           and region(ev) == r)] for m, r in keys]
    return sorted(out, key=lambda x: -x[2])


def idle_split(t: Dict) -> List[List]:
    """Device idle time in the window split by what the host was doing at
    each instant of it: the innermost span open then (``engine.*`` or
    ``bench_*``), or ``harness`` where none was.  ``trace.idle_gaps``
    gives each whole gap to the span open at its middle."""
    w0, w1 = trace.window(t)
    planes = sorted({ev[0] for ev in t["device"]})
    host = [h for h in t["host"] if h[0] != trace.WINDOW_SPAN]
    acc: Dict[str, float] = {}
    for plane in planes:
        busy = trace._union([(s, e) for ev, s, e in
                             trace._clipped(t["device"], w0, w1)
                             if ev[0] == plane])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            over = [h for h in host if h[1] < b and h[1] + h[2] > a]
            cuts = sorted({a, b} | {x for h in over for x in
                                    (h[1], h[1] + h[2]) if a < x < b})
            for s, e in zip(cuts, cuts[1:]):
                mid = 0.5 * (s + e)
                open_ = [h for h in over if h[1] <= mid <= h[1] + h[2]]
                name = (min(open_, key=lambda h: h[2])[0] if open_
                        else "harness")
                acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9
    scale = 1.0 / max(len(planes), 1)
    return [[k, v * scale] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])]
