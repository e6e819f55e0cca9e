"""The engine's spans in a real profiler trace, and the reduction of the
program's phases (``harness.phases``): host self time per step, prefill
block time, decode attention time and idle time by engine phase."""
import glob
import gzip
import json
import os

import jax
import numpy as np
import pytest

from harness import phases, trace

MS = 1_000_000          # ns
SPANS = ("engine.step", "engine.schedule", "engine.decode.prep",
         "engine.decode.launch", "engine.decode.fetch",
         "engine.decode.commit", "engine.admit", "engine.prefill.launch",
         "engine.prefill.fetch")


# -- the program's spans, traced on the CPU ----------------------------------

def _engine(cfg, params):
    from repro.engine import Engine
    return Engine(params, cfg, n_slots=2, page_size=8, max_seq=64,
                  prefill_chunk=16)


def _requests(vocab):
    from repro.engine import Request
    rng = np.random.default_rng(3)
    # prompts of 1, 2 and 3 blocks (the last one short)
    return [Request(rid=100 + r, prompt=rng.integers(0, vocab, n),
                    max_new_tokens=3)
            for r, n in enumerate((16, 24, 40))]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny engine serving three requests under a profiler session,
    read back with ``phases.load`` (its programs compiled beforehand)."""
    import bench_tiny
    from repro.models.transformer import init_params
    cfg = bench_tiny.program_config()
    params = init_params(jax.random.PRNGKey(0), cfg)
    _engine(cfg, params).run(_requests(cfg.vocab))
    d = tmp_path_factory.mktemp("xplane")
    eng = _engine(cfg, params)
    reqs = _requests(cfg.vocab)
    with jax.profiler.trace(str(d)):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            eng.run(reqs)
    path = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)[0]
    return phases.load(path), reqs, eng


def _inside(inner, outer):
    return (outer[1] <= inner[1]
            and inner[1] + inner[2] <= outer[1] + outer[2])


def _names(t):
    return {h[0] for h in t["host"] if h[0].startswith("engine.")}


def _nested(t):
    steps = [h for h in t["host"] if h[0] == "engine.step"]
    return all(any(_inside(h, s) for s in steps)
               for h in t["host"] if h[0].startswith("engine."))


def _fetch_in_phase(t):
    """Each fetch lies in a step after its phase's launch: the decode
    fetch between the step's decode launch and commit, the prefill fetch
    after the same request's final block."""
    host = [h for h in t["host"] if h[0].startswith("engine.")]
    for step in (h for h in host if h[0] == "engine.step"):
        kids = [h for h in host if h is not step and _inside(h, step)]
        by = {}
        for h in kids:
            by.setdefault(h[0], []).append(h)
        for f in by.get("engine.decode.fetch", []):
            (launch,), (commit,) = (by["engine.decode.launch"],
                                    by["engine.decode.commit"])
            if not (launch[1] + launch[2] <= f[1]
                    and f[1] + f[2] <= commit[1]):
                return False
        for f in by.get("engine.prefill.fetch", []):
            mine = [h for h in by["engine.prefill.launch"]
                    if h[3]["rid"] == f[3]["rid"]
                    and h[1] + h[2] <= f[1]]
            if not mine:
                return False
    return True


def _prefill_rids(t, reqs):
    """Prefill spans carry their request's rid; the launches also give
    each block's start and width, which tile the prompt."""
    blocks = {}
    for h in t["host"]:
        if h[0] == "engine.prefill.launch":
            blocks.setdefault(h[3]["rid"], []).append(
                (h[3]["start"], h[3]["width"]))
    fetched = sorted(h[3]["rid"] for h in t["host"]
                     if h[0] == "engine.prefill.fetch")
    want = {r.rid: r.prompt_len for r in reqs}
    tiles = all(sorted(b)[0][0] == 0 and sum(w for _, w in b) == want[rid]
                and all(s0 + w0 == s1 for (s0, w0), (s1, _)
                        in zip(sorted(b), sorted(b)[1:]))
                for rid, b in blocks.items())
    return set(blocks) == set(want) and fetched == sorted(want) and tiles


@pytest.mark.parametrize("check", ["names", "nested", "fetch_in_phase",
                                   "prefill_rids", "steps_numbered"])
def test_engine_spans(traced, check):
    t, reqs, eng = traced
    if check == "names":
        assert _names(t) == set(SPANS)
    elif check == "nested":
        assert _nested(t)
    elif check == "fetch_in_phase":
        assert _fetch_in_phase(t)
    elif check == "prefill_rids":
        assert _prefill_rids(t, reqs)
    else:
        steps = [h[3]["step"] for h in t["host"] if h[0] == "engine.step"]
        assert steps == list(range(1, eng.stats.steps + 1))


# -- the reduction, on a trace made by hand ---------------------------------

DEC, PRE = phases.DECODE_MODULE, phases.PREFILL_MODULE


def _op(name, start, dur, module, scope):
    return ["/device:TPU:0", f"%{name} = f32[8] {name}()", name,
            start * MS, dur * MS, module, scope]


def _made():
    """A 20 ms window, two engine steps.  Step 1 (0-9 ms) decodes: its
    fetch 1.5-7 waits on the decode program (embed 1.2-1.6, a layer loop
    1.6-6 holding attn 1.6-4 and mlp 4-6, head 6-6.8, sample 6.8-7).
    Step 2 (10-19 ms) decodes (fetch 11.5-16, attn 11.6-14, mlp 14-15.9)
    and runs the final prefill block of request 5 (launch 17-17.5,
    prefill program 17.6-19, fetch 17.5-19)."""
    host = [["bench_window", 0.0, 20 * MS], ["bench_step", 0.0, 9.2 * MS],
            ["bench_step", 9.8 * MS, 9.4 * MS]]
    for n, t0, fetch in ((1, 0.0, 5.5), (2, 10.0, 4.5)):
        host += [["engine.step", t0 * MS, 9 * MS, {"step": n}],
                 ["engine.schedule", t0 * MS, 0.5 * MS, {}],
                 ["engine.decode.prep", (t0 + .5) * MS, .5 * MS,
                  {"live": 8}],
                 ["engine.decode.launch", (t0 + 1) * MS, .5 * MS, {}],
                 ["engine.decode.fetch", (t0 + 1.5) * MS, fetch * MS, {}],
                 ["engine.decode.commit", (t0 + 1.5 + fetch) * MS,
                  .5 * MS, {}],
                 ["engine.admit", (t0 + 2 + fetch) * MS, .5 * MS, {}]]
    host += [["engine.prefill.launch", 17 * MS, .5 * MS,
              {"rid": 5, "start": 128, "width": 64}],
             ["engine.prefill.fetch", 17.5 * MS, 1.5 * MS, {"rid": 5}]]
    device = [_op("gather", 1.2, .4, DEC, "embed"),
              _op("while", 1.6, 4.4, DEC, "while"),
              _op("attention", 1.6, 2.4, DEC, "while/body/attn"),
              _op("matmul", 4, 2, DEC, "while/body/mlp"),
              _op("head", 6, .8, DEC, "head"),
              _op("sort", 6.8, .2, DEC, "sample"),
              _op("attention", 11.6, 2.4, DEC, "while/body/attn"),
              _op("matmul", 14, 1.9, DEC, "while/body/mlp"),
              _op("block", 17.6, 1.4, PRE, "prefill/while/body/attn")]
    return {"device": device, "host": host}


def test_step_host_ms_leaves_out_the_fetches():
    # (9 - 5.5) and (9 - 4.5 - 1.5)
    assert phases.step_host_ms(_made()) == pytest.approx(3.25)


def test_prefill_block_ms_per_launch():
    assert phases.prefill_block_ms(_made()) == pytest.approx(1.4)


def test_decode_attn_ms_per_decode_step():
    t = _made()
    assert phases.decode_attn_ms(t) == pytest.approx(2.4)
    # the layer loop that holds the attention ops is not attention
    t["device"][1][6] = "while/body/attn"
    assert phases.decode_attn_ms(t) == pytest.approx(2.4)


def test_scope_seconds_by_program_and_region():
    got = {(m, r): s for m, r, s in phases.scope_seconds(_made())}
    assert got == pytest.approx({
        (DEC, "embed"): .0004, (DEC, "attn"): .0048, (DEC, "mlp"): .0039,
        (DEC, "head"): .0008, (DEC, "sample"): .0002,
        (PRE, "prefill"): .0014})


@pytest.mark.parametrize("spans,want", [
    # gaps 0-1.2 (in the decode prep), 7-11.6 and 19-20 (between
    # steps), 15.9-17.6 (in admission)
    ("program", {"engine.decode.prep": .0012, "engine.admit": .0017,
                 "harness": .0056}),
    ("harness", {"bench_step": .0029, "harness": .0056})])
def test_idle_gaps_by_engine_phase(spans, want):
    """With the program's spans loaded, the idle time inside a step moves
    from the harness's span to the engine phase that was running."""
    t = _made()
    if spans == "harness":
        t["host"] = [h for h in t["host"] if h[0].startswith("bench_")]
    assert dict(trace.idle_gaps(t)) == pytest.approx(want)


def test_idle_split_by_the_span_open_at_each_instant():
    """Each idle gap is cut where a span opens or closes, and each piece
    goes to the innermost span open over it."""
    got = dict(phases.idle_split(_made()))
    assert got == pytest.approx({
        "engine.schedule": .001, "engine.decode.prep": .001,
        "engine.decode.launch": .0007, "engine.decode.fetch": .0002,
        "engine.decode.commit": .001, "engine.admit": .001,
        "engine.step": .001, "engine.prefill.launch": .0005,
        "engine.prefill.fetch": .0001, "bench_step": .0006,
        "harness": .0014})
    b = trace.busy(_made())
    assert sum(got.values()) == pytest.approx(b["window_s"] - b["busy_s"])


@pytest.mark.parametrize("metric", ["step_host_ms", "prefill_block_ms",
                                    "decode_attn_ms"])
def test_a_trace_without_program_spans_reads_nothing(metric):
    """The harness's own trace of a program with no spans or scopes:
    every reduction returns None and none raises."""
    t = _made()
    t["host"] = [h for h in t["host"] if h[0].startswith("bench_")]
    t["device"] = [ev[:5] for ev in t["device"]]
    assert getattr(phases, metric)(t) is None


# -- the loader, on an xplane written from its text form ---------------------

XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000500
      stats { metadata_id: 11 uint64_value: 7 } }
    events { metadata_id: 3 offset_ps: 1000000700 duration_ps: 2000000000 }
    events { metadata_id: 4 offset_ps: 4100000000 duration_ps: 800000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit__decode_and_sample(77)" } }
  event_metadata { key: 2 value { id: 2 name: "%while.1 = (f32[8]) while()"
    stats { metadata_id: 10 str_value: "77" }
    stats { metadata_id: 12 str_value: "jit(_decode_and_sample)/while:" } } }
  event_metadata { key: 3 value { id: 3
    name: "%_paged_attention_jit.9 = f32[8,36,64] custom-call()"
    stats { metadata_id: 10 str_value: "77" }
    stats { metadata_id: 12 str_value:
      "jit(_decode_and_sample)/while/body/closed_call/attn/"
      "jit(_paged_attention_jit)/pallas_call:" } } }
  event_metadata { key: 4 value { id: 4 name: "%copy.26 = u32[8,288] copy()"
    stats { metadata_id: 10 str_value: "77" }
    stats { metadata_id: 12 str_value: "params['embed_tok_pidx']:" } } }
  stat_metadata { key: 10 value { id: 10 name: "program_id" } }
  stat_metadata { key: 11 value { id: 11 name: "run_id" } }
  stat_metadata { key: 12 value { id: 12 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 6000000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 5000000000 }
    events { metadata_id: 3 offset_ps: 200000 duration_ps: 4900000000
      stats { metadata_id: 10 int64_value: 4 } }
    events { metadata_id: 4 offset_ps: 300000 duration_ps: 100000
      stats { metadata_id: 11 int64_value: 9 }
      stats { metadata_id: 12 int64_value: 64 }
      stats { metadata_id: 13 int64_value: 64 } }
    events { metadata_id: 5 offset_ps: 400000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "bench_step" } }
  event_metadata { key: 3 value { id: 3 name: "engine.step" } }
  event_metadata { key: 4 value { id: 4 name: "engine.prefill.launch" } }
  event_metadata { key: 5 value { id: 5 name: "PjitFunction(f)" } }
  stat_metadata { key: 10 value { id: 10 name: "step" } }
  stat_metadata { key: 11 value { id: 11 name: "rid" } }
  stat_metadata { key: 12 value { id: 12 name: "start" } }
  stat_metadata { key: 13 value { id: 13 name: "width" } }
}
"""


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("xspace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(path)


def test_load_keeps_what_trace_load_reads(xplane):
    """``phases.load`` lists the same ops, at the same whole-nanosecond
    times and with the same names and stats, and the same ``bench_*``
    spans as ``trace.load``."""
    old, new = trace.load(xplane), phases.load(xplane)
    assert [ev[:5] for ev in new["device"]] == old["device"]
    assert [h for h in new["host"] if len(h) == 3] == old["host"]
    # 1000 ns + 1000000700 ps, cut to whole nanoseconds
    assert old["device"][1][3:] == [1001000.0, 2000000.0]


def test_load_adds_modules_scopes_and_program_spans(xplane):
    t = phases.load(xplane)
    assert [ev[5:] for ev in t["device"]] == [
        [DEC, ""], [DEC, "while/body/closed_call/attn"], [DEC, ""]]
    assert [phases.region(ev) for ev in t["device"]] == [
        "unscoped", "attn", "unscoped"]
    assert [h for h in t["host"] if len(h) == 4] == [
        ["engine.step", 700.0, 4900000.0, {"step": 4}],
        ["engine.prefill.launch", 800.0, 100.0,
         {"rid": 9, "start": 64, "width": 64}]]


# -- a recorded trace --------------------------------------------------------

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_spans.json.gz")


def _covered(t, keep):
    """Seconds of the window in which an op ``keep`` accepts ran: a plain
    sweep over the ops' start and end points."""
    w0, w1 = trace.window(t)
    points = sorted(p for ev in t["device"] if keep(ev)
                    for p in ((max(ev[3], w0), 1),
                              (min(ev[3] + ev[4], w1), -1))
                    if max(ev[3], w0) < min(ev[3] + ev[4], w1))
    total, depth, since = 0.0, 0, None
    for x, step in points:
        if depth == 0 and step == 1:
            since = x
        depth += step
        if depth == 0:
            total += x - since
    return total * 1e-9


def test_recorded_spans():
    """Three steps of MiniCPM-2B on one v5e, read with ``phases.load``
    (op text cut short): each decodes 8 slots, and request 7's two
    prefill blocks run in the first two (the second step's decode launch
    waits for the first block); checked against plain recomputations."""
    with gzip.open(FIXTURE, "rt") as f:
        t = json.load(f)
    steps = phases.spans(t, phases.STEP)
    assert [h[3]["step"] for h in steps] == [31, 32, 33]
    assert [h[3] for h in t["host"] if h[0] == "engine.prefill.launch"] \
        == [{"rid": 7, "start": 0, "width": 64},
            {"rid": 7, "start": 64, "width": 64}]
    fetch = [h for h in t["host"] if phases.FETCH.fullmatch(h[0])]
    assert phases.step_host_ms(t) == pytest.approx(np.mean(
        [s[2] - sum(f[2] for f in fetch if _inside(f, s))
         for s in steps]) * 1e-6)
    assert phases.step_host_ms(t) < np.mean([s[2] for s in steps]) * 1e-6
    attn = _covered(t, lambda ev: ev[5] == DEC
                    and phases.region(ev) == "attn")
    assert phases.decode_attn_ms(t) == pytest.approx(attn / 3 * 1e3)
    decode = _covered(t, lambda ev: ev[5] == DEC)
    assert 0 < phases.decode_attn_ms(t) < decode / 3 * 1e3
    prefill = _covered(t, lambda ev: ev[5] == PRE)
    assert phases.prefill_block_ms(t) == pytest.approx(prefill / 2 * 1e3)
    b = trace.busy(t)
    assert 2 * phases.prefill_block_ms(t) * 1e-3 <= b["busy_s"]
    # the prefill program is one scope, but for its parameters' copies
    pre = {r: v for m, r, v in phases.scope_seconds(t) if m == PRE}
    assert set(pre) <= {"prefill", "unscoped"}
    assert pre["prefill"] >= 0.99 * sum(pre.values())
    assert sum(v for _, _, v in phases.scope_seconds(t)) == \
        pytest.approx(b["busy_s"], rel=0.01)
    idle = b["window_s"] - b["busy_s"]
    split = dict(phases.idle_split(t))
    assert sum(split.values()) == pytest.approx(idle)
    assert sum(v for k, v in split.items() if k.startswith("engine.")) \
        >= 0.9 * idle
    gaps = dict(trace.idle_gaps(t))
    assert sum(v for k, v in gaps.items() if k.startswith("engine.")) \
        >= 0.9 * sum(gaps.values())
