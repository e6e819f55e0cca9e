"""One run of one cell: set-up, the measured window, the metrics and the
correctness check.

Set-up builds the served weights on the device in one jitted call from
the seed, warms up the cell's programs on a throw-away engine (one
decode program, one prefill program per block width the run's prompts
produce, and sampling), then builds the engine the window drives; the
engine's jits are module-level, so nothing compiles again.  ``setup_s``
runs from the start of the process to the window's opening, warm-in
included.  After the window the state is freed, and the reference is run
over a sample of the finished requests.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from harness import client, reference, traffic, weights, work
from harness.spec import BENCH_DIR, ModelSpec, metrics_for

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class BenchError(Exception):
    """A run that cannot give a result (no chip, a broken set-up)."""


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    spec: ModelSpec
    mix: Dict
    rec: client.Record
    seconds: float
    setup_s: float
    peaks: Dict
    trace: Optional[Dict] = None       # harness.trace.load() output
    traced: tuple = ()                 # (start, stop) of the traced window

    def traced_steps(self):
        t0, t1 = self.traced
        return [s for s in self.rec.steps if t0 <= s.end <= t1]


class CompileLog:
    """Times (host clock) of JAX's compile events while it is open."""

    def __init__(self):
        self.times: List[float] = []

    def _event(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.times.append(time.perf_counter())

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._event)

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


def check_model(spec: ModelSpec, model: str):
    """The configuration file and the program's registry must agree."""
    from repro.configs import get_config
    cfg = get_config(model)
    want = dict(d_model=spec.d_model, n_heads=spec.n_heads, n_kv=spec.n_kv,
                head_dim=spec.head_dim, d_ff=spec.d_ff, vocab=spec.vocab,
                n_layers=spec.layers, rope_theta=spec.rope_theta,
                qkv_bias=spec.qkv_bias, emb_scale=spec.emb_scale,
                tie_embeddings=True, mlp_act="silu", gated_mlp=True)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise BenchError(f"configuration file disagrees with repro.configs "
                         f"{model!r}: file {want}, program {got}")
    return cfg


def make_engine(params, cfg, spec: ModelSpec):
    from repro.engine import Engine
    return Engine(params, cfg, n_slots=spec.n_slots,
                  page_size=spec.page_size, max_seq=spec.max_seq,
                  n_pages=spec.n_pages, prefill_chunk=spec.prefill_chunk,
                  kv_bits=spec.kv_bits)


def make_request(rid: int, item: traffic.Item):
    from repro.engine import Request
    return Request(rid=rid, prompt=item.prompt, max_new_tokens=item.max_new,
                   temperature=0.0, seed=rid)


def block_widths(items: List[traffic.Item], chunk: int) -> List[int]:
    """Every prefill block width the prompts of ``items`` produce."""
    widths = set()
    for it in items:
        n = len(it.prompt)
        widths.add(min(chunk, n))
        if n > chunk and n % chunk:
            widths.add(n % chunk)
    return sorted(widths)


def warm_up(params, cfg, spec: ModelSpec, widths: List[int]):
    """Compile the cell's programs on an engine that is then dropped."""
    eng = make_engine(params, cfg, spec)
    for i, w in enumerate(widths):
        eng.submit(make_request(-1 - i, traffic.Item(
            np.zeros((w,), np.int32), 2)))
    while eng.sched.has_work():
        eng.step()
    del eng
    gc.collect()


def load_metric(name: str) -> Callable:
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(f"metric_{name}",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def reference_sample(rec: client.Record, outputs: Dict[int, np.ndarray],
                     prompts: Dict[int, np.ndarray], n: int, seed: int):
    """``n`` requests the run finished, drawn from the seed, the longest
    always among them."""
    done = sorted(r.rid for r in rec.reqs.values()
                  if r.rid in outputs and r.outcome == "finished")
    if not done:
        return []
    longest = max(done, key=lambda r: (len(prompts[r]) + len(outputs[r]),
                                       -r))
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng([int(seed), 0x5EF])
    pick = [longest] + list(rng.choice(rest, size=min(n - 1, len(rest)),
                                       replace=False))
    return [(prompts[r], outputs[r]) for r in sorted(pick)]


def run_cell(cell: Dict, config: Dict, mix: Dict, seed: int,
             seconds: float, trace: bool, t_start: float,
             require_tpu: bool = True, hooks: Optional[Dict] = None,
             log=print, controls: Sequence[str] = ()) -> Dict:
    """One run.  Each of ``controls`` (a lower precision of the
    reference, see ``reference.compare``) is judged by the same checks
    as the program, in the program's place, under ``"controls"``."""
    import jax
    from harness import trace as tr
    from harness.peaks import peaks as peak_table

    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu" or len(devs) < cell["chips"]):
        raise BenchError(f"cell {cell['name']} needs {cell['chips']} TPU "
                         f"chip(s); JAX found {len(devs)} {dev.platform} "
                         f"device(s)")
    peaks = peak_table(dev.device_kind) if require_tpu else {
        "bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    spec = ModelSpec.from_config(config)
    cfg = check_model(spec, config["model"])
    key = weights.seed_key(seed)

    items = traffic.items(mix, seed, spec.vocab)
    prompts = {}

    def make(rid, item):
        prompts[rid] = item.prompt
        return make_request(rid, item)

    with CompileLog() as compiles:
        params = jax.block_until_ready(weights.serving_tree(key, spec))
        warm_up(params, cfg, spec, block_widths(items,
                                                spec.prefill_chunk))
        engine = make_engine(params, cfg, spec)
        tracer = tr.Tracer(mix["trace_s"]) if trace else None
        loop = client.Loop(engine, mix, make, hooks=hooks, tracer=tracer)
        jit_before = engine.trace_counts()
        rec = loop.run(items, seconds)
        jit_after = engine.trace_counts()
        in_window = compiles.between(rec.t0, rec.t1)
    new_jits = sum(jit_after[k] - jit_before[k] for k in jit_after)
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    outputs = dict(engine.outputs)

    run = Run(spec=spec, mix=mix, rec=rec, seconds=seconds,
              setup_s=rec.t0 - t_start, peaks=peaks)
    if tracer is not None:
        run.trace = tracer.load()
        run.traced = (tracer.t_start, tracer.t_stop)
    report_routes(engine, spec, rec, in_window, new_jits, log)
    del engine, params, loop
    gc.collect()

    metrics = {}
    for m in metrics_for(cell["name"], trace):
        value = load_metric(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    attempted = rec.in_window()
    failed = sum(1 for r in attempted
                 if not r.times or r.outcome not in ("finished", ""))
    t_ref = time.perf_counter()
    sample = reference_sample(rec, outputs, prompts,
                              mix["reference_requests"], seed)
    if hooks and "sample" in hooks:
        hooks["sample"](key, spec, sample)
    readings = (reference.compare(key, spec, sample, controls) if sample
                else {})
    log(f"reference: {len(sample)} requests, "
        f"{sum(len(s) for _, s in sample)} served tokens, "
        f"{time.perf_counter() - t_ref:.1f} s: {json.dumps(readings)}")
    limits = config["correct"]
    compiled = in_window + new_jits
    correct, checks = judge(readings.get("program"), limits, compiled)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    out = {"correct": correct, "attempted": len(attempted),
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        summ = tr.summary(run.trace)
        if summ is None:
            raise BenchError("the trace holds no device op in the window")
        device["busy_s"] = summ["busy_s"]
        device["window_s"] = summ["window_s"]
        out["breakdown"] = {"device_ops": summ["device_ops"],
                            "idle_gaps": summ["idle_gaps"]}
    if controls:
        out["controls"] = {}
        for c in controls:
            ok, chk = judge(readings.get(c), limits, compiled)
            out["controls"][c] = {"correct": ok, "checks": chk}
    out["checks"] = checks
    return out


def judge(stats: Optional[Dict], limits: Dict, compiled: int):
    """(correct, checks): each number the configuration limits, from the
    served tokens' ``stats``, beside its limit, and the compiles inside
    the window (limit 0).  No sample reads infinite."""
    checks = {name: {"value": (stats[name] if stats else float("inf")),
                     "limit": limit} for name, limit in limits.items()}
    checks["compiles_in_window"] = {"value": compiled, "limit": 0}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def report_routes(engine, spec, rec: client.Record, in_window: int,
                  new_jits: int, log):
    """The program's route counters and the harness's own, on one line:
    kernel traces by mode, jnp routes taken, jit entries, compiles inside
    the window (must be 0), how late the generator ran, the KV pages in
    use over the window (and the bytes they hold, one copy of the pool),
    and numbers no bound holds (see PERF.md): the 95th percentile of the
    token gaps and the time to first token of the requests due in the
    window."""
    from repro.kernels import dispatch, ops
    late = np.asarray(rec.lateness) if rec.lateness else np.zeros(1)
    ttft = client.ttfts(rec)
    p50 = client.percentile(ttft, 50)
    pages = np.asarray([s.pages for s in rec.window_steps()] or [0])
    page_bytes = (spec.page_size * spec.layers
                  * work.kv_bytes_per_position(spec)
                  + spec.layers * work.kv_codebook_bytes_per_page(spec))
    log("routes: " + json.dumps({
        "kernel_traces": dict(ops.CALLS),
        "jnp_routes": dict(dispatch.FALLBACKS),
        "jit_entries": engine.trace_counts(),
        "compiles_in_window": in_window,
        "jit_entries_added_in_window": new_jits,
        "generator_late_ms": {"mean": float(late.mean() * 1e3),
                              "p99": float(np.percentile(late, 99) * 1e3),
                              "max": float(late.max() * 1e3)},
        "requests": len(rec.reqs), "steps": len(rec.steps),
        "window_steps": len(rec.window_steps()),
        "window_requests": len(rec.due_in_window()),
        "kv_pages_used": {"mean": float(pages.mean()),
                          "max": int(pages.max()),
                          "of": engine.pool.n_pages,
                          "live_bytes_mean": float(pages.mean()
                                                   * page_bytes)},
        "preemptions": engine.stats.preemptions,
        "itl_p95_ms": 1e3 * (client.percentile(client.token_gaps(rec), 95)
                             or 0.0),
        "ttft_ms": {"n": len(ttft), "p50": None if p50 is None else p50 * 1e3,
                    "max": max(ttft) * 1e3 if ttft else None}}))


def setup_jax(root: str):
    """Compile cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def print_checks(checks: Dict):
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
