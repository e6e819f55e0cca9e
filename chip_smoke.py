#!/usr/bin/env python3
"""Smoke run of packed-codebook serving of qwen1.5-0.5b on a TPU.

    python chip_smoke.py                # one chip: the main serving path
    python chip_smoke.py --four-chips   # only the mesh paths, on four chips

One chip, at the full width of qwen1.5-0.5b (24 layers, d_model 1024,
vocab 151936, tied embeddings; random f32 weights from ``--seed``):

1. build: ``init_params`` → ``CompressionPlan.parse("adaptive:16")``
   ``.init`` (direct compression: k-means C step on the chip) → ``pack``
   → ``PackedModel`` save/load round trip → ``serving_params(packed=True)``;
   the same for ``adaptive:2``;
2. serve: ``Engine`` on a mesh of the one device — 4 slots, 8 requests
   with seeded prompts of 64–256 tokens, 32 greedy tokens each, dense KV;
   every request must finish, with one decode compile;
3. check: each request's prompt + generated tokens through
   ``transformer.forward`` on the decoded f32 weights (matmul precision
   "highest", no cache).  Every generated token must be the reference
   argmax wherever the reference's top-1/top-2 margin exceeds twice the
   logit tolerance; ``prefill`` on the packed params must match the
   reference logits at the last prompt position within that tolerance;
4. quantized KV: K=2 weights, ``kv_bits=4``, same traffic; every request
   must finish (the engine fails any request whose logits go
   non-finite), and ``paged_attention_quant`` / ``blockwise_prefill_quant``
   on that engine's pools must match their jnp references.

``--four-chips`` runs only the mesh paths, on qwen1.5-0.5b cut to
FOUR_CHIP_LAYERS layers (full width and vocabulary): the sharded C step
on a (data=2, model=2) mesh against the unsharded one, and the engine
on a (data=1, model=4) mesh against the f32 reference (the same
teacher-forced rule) and against the same engine on one of the chips.

Any failed check prints its reason and exits 1.  So does a run where JAX
finds no TPU, where the kernels do not dispatch to Mosaic (backend
``pallas``), or where any kernel ran in interpret mode.  The last line of
a passing run is ``{"ok": true, "device": {...}}``.
"""
import argparse
import contextlib
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1.5-0.5b"
N_SLOTS, N_REQUESTS, GEN, PAGE = 4, 8, 32, 16
# Multiples of 32: the engine's 64-token prefill blocks then come in two
# widths (64 and 32), so prefill compiles twice, not once per prompt.
PROMPT_LENS = tuple(range(64, 257, 32))
MAX_SEQ = max(PROMPT_LENS) + GEN
# Logit tolerance as a fraction of the reference logits' RMS: rounding
# f32 matmuls through bf16 passes (~2^-9 relative per product) over 24
# layers stays at a few percent of the RMS, while a wrong kernel, layout
# or dequant moves the logits by about their whole RMS.  The check also
# prints XLA's own gap at default matmul precision on the same weights,
# the witness for what bf16-pass rounding alone costs.
LOGIT_TOL_REL = 0.1
# Quantized-KV attention kernels vs their jnp references, relative to the
# largest |output|: bf16-pass rounding of q·k and p·v is ~0.4%; a wrong
# page, codebook or lane order is an O(1) error.
ATTN_TOL_REL = 2e-2
# Sharded vs unsharded C step: codebooks differ only by the order of the
# f32 sums in the k-means statistics.  One reordered f32 sum over ~1e8
# weights is off by up to ~1e-7 x N^0.5 relative, and the warm C step's
# 5 k-means iterations carry it through reassignments at cell edges:
# 1.64e-5 was measured at qwen1.5-0.5b scale on four v5e chips, so 1e-4
# bounds it, while a wrong shard, psum or scheme moves a centroid by a
# fraction of the cell width (~1e-1 relative).  Assignments may differ
# only within this tolerance of a cell edge.
CB_TOL_REL = 1e-4
# Depth of the --four-chips model: the mesh paths are per layer, so a
# cut depth tests them at full width for a fraction of the build time.
FOUR_CHIP_LAYERS = 4


class SmokeError(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeError(msg)


def log(msg):
    print(msg, flush=True)


class Phases:
    """Wall time per phase, with JAX's compile time (lowering to MLIR and
    the backend compile; tracing nests and is left in the run) counted
    apart from the run."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.rows = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, secs, **_):
        if event in ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                     "/jax/core/compile/backend_compile_duration"):
            self.compile_s += secs

    @contextlib.contextmanager
    def __call__(self, name):
        c0, t0 = self.compile_s, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        self.rows.append((name, wall, comp))
        log(f"phase {name}: {wall:.1f} s wall = {comp:.1f} s compile + "
            f"{wall - comp:.1f} s run")


def build_artifact(plan_spec, params, key, keep_state=False):
    """Direct compression → PackedModel → save/load round trip."""
    from repro.core import CompressionPlan, PackedModel

    import jax

    t0 = time.perf_counter()
    plan = CompressionPlan.parse(plan_spec)
    qspec = plan.build_qspec(params)
    state = jax.block_until_ready(plan.init(key, params, qspec))
    t1 = time.perf_counter()
    packed = plan.pack(params, state, qspec)
    t2 = time.perf_counter()
    path = packed.save(os.path.join(HERE, "out", "chip_smoke",
                                    plan_spec.replace(":", "_")))
    loaded = PackedModel.load(path)
    log(f"artifact {plan_spec}: direct-compression C step {t1 - t0:.1f} s,"
        f" pack {t2 - t1:.1f} s, save+load {time.perf_counter() - t2:.1f} s")
    s = loaded.summary()
    check(s == packed.summary(), f"{plan_spec}: artifact changed on "
                                 f"save/load")
    log(f"artifact {plan_spec}: eq.14 ratio x{s['ratio']:.2f} over "
        f"{loaded.bits_ref}-bit floats ({s['bits_per_weight']} bit/weight; "
        f"p1={s['p1']} quantized, p0={s['p0']} dense, "
        f"{s['codebook_entries']} codebook floats; {s['packed_bytes']} B "
        f"vs {s['ref_bytes']} B)")
    return (loaded, plan, qspec, state) if keep_state else loaded


def make_requests(cfg, seed):
    import numpy as np
    from repro.engine import Request

    rng = np.random.RandomState(seed)
    lens = rng.choice(PROMPT_LENS, N_REQUESTS)
    return [Request(rid=i, prompt=rng.randint(0, cfg.vocab, size=int(n)),
                    max_new_tokens=GEN, temperature=0.0, seed=seed + i)
            for i, n in enumerate(lens)]


def place(params, mesh):
    import jax
    from repro.dist import sharding as shard_rules

    return jax.tree_util.tree_map(jax.device_put, params,
                                  shard_rules.param_shardings(params, mesh))


def serve(params, cfg, reqs, mesh, kv_bits):
    """Run every request through the engine; all must finish, with one
    decode compile.  Returns (rid → generated tokens, engine)."""
    from repro.engine import Engine
    from repro.engine import engine as engine_mod
    from repro.engine.outcomes import Outcome
    from repro.models import sharding_ctx

    before = engine_mod._DECODE._cache_size()
    policy = sharding_ctx.Policy(mesh, mode="tp")
    with mesh, sharding_ctx.activation_policy(policy):
        eng = Engine(place(params, mesh), cfg, n_slots=N_SLOTS,
                     page_size=PAGE, max_seq=MAX_SEQ, mesh=mesh,
                     kv_bits=kv_bits)
        outs = eng.run(list(reqs))
    res = eng.results
    done = sum(r.outcome is Outcome.FINISHED for r in res.values())
    log(f"engine mesh {dict(mesh.shape)} kv_bits={kv_bits}: "
        f"{done}/{len(reqs)} FINISHED, {eng.stats.steps} steps, "
        f"{eng.stats.delivered_tokens} tokens")
    bad = {rid: f"{r.outcome.value}: {r.detail}" for rid, r in res.items()
           if r.outcome is not Outcome.FINISHED}
    check(not bad and len(outs) == len(reqs), f"requests not FINISHED: {bad}")
    check(all(len(outs[r.rid]) == GEN for r in reqs),
          "a stream is not max_new_tokens long")
    compiles = eng.decode_compile_count() - before
    log(f"engine decode compiles: {compiles}")
    check(compiles == 1, f"engine compiled decode {compiles} times")
    return outs, eng


def reference_check(dense, packed_params, cfg, reqs, outs, label):
    """Teacher-forced f32 reference over prompt + generated tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.transformer import forward, prefill

    @functools.partial(jax.jit, static_argnums=1)
    def ref_rows(p, cfg, tokens, last):
        logits = forward(p, cfg, tokens)[0]                   # [S, V]
        vals, idx = jax.lax.top_k(logits, 2)
        return vals, idx, logits[last]

    @functools.partial(jax.jit, static_argnums=1)
    def packed_row(p, cfg, tokens, last):
        logits, _ = prefill(p, cfg, tokens)
        return logits[0, last]

    @functools.partial(jax.jit, static_argnums=1)
    def xla_row(p, cfg, tokens, last):
        return forward(p, cfg, tokens)[0, last]

    rows = []
    for r in reqs:
        p_len = r.prompt.size
        seq = np.zeros((MAX_SEQ,), np.int32)
        seq[:p_len] = r.prompt
        seq[p_len:p_len + GEN] = outs[r.rid]
        # causal model: tokens past a position never change its logits,
        # so one padded shape serves every request
        with jax.default_matmul_precision("highest"):
            vals, idx, ref_last = ref_rows(dense, cfg,
                                           jnp.asarray(seq[None]),
                                           p_len - 1)
        prompt = jnp.asarray(seq[None, :max(PROMPT_LENS)])
        got_last = packed_row(packed_params, cfg, prompt, p_len - 1)
        xla_last = xla_row(dense, cfg, prompt, p_len - 1)
        rows.append((r, np.asarray(vals), np.asarray(idx),
                     np.asarray(ref_last), np.asarray(got_last),
                     np.asarray(xla_last)))
    rms = float(np.sqrt(np.mean([np.mean(row[3] ** 2) for row in rows])))
    tol = LOGIT_TOL_REL * rms
    dmax = max(float(np.max(np.abs(row[4] - row[3]))) for row in rows)
    dxla = max(float(np.max(np.abs(row[5] - row[3]))) for row in rows)
    checked, wrong = 0, []
    for r, vals, idx, *_ in rows:
        for i, tok in enumerate(outs[r.rid]):
            pos = r.prompt.size - 1 + i
            if vals[pos, 0] - vals[pos, 1] > 2 * tol:
                checked += 1
                if int(idx[pos, 0]) != int(tok):
                    wrong.append((r.rid, i, int(tok), int(idx[pos, 0])))
    log(f"{label}: max |dlogit| at the last prompt position (packed "
        f"prefill vs f32 reference) = {dmax:.4g}; tolerance {tol:.4g} "
        f"({LOGIT_TOL_REL:g} x reference logit RMS {rms:.4g})")
    log(f"{label}: witness: XLA forward on the same decoded weights at "
        f"default matmul precision is off the reference by {dxla:.4g} "
        f"({dxla / rms:.3g} x RMS; packed kernels: {dmax / rms:.3g} x "
        f"RMS)")
    log(f"{label}: teacher-forced check: {checked}/{N_REQUESTS * GEN} "
        f"generated tokens have a reference margin > {2 * tol:.4g}; "
        f"{len(wrong)} of them differ from the reference argmax")
    check(np.isfinite(dmax) and dmax <= tol,
          f"{label}: packed prefill logits off by {dmax:.4g} > {tol:.4g}")
    check(checked > 0, f"{label}: no generated token had a reference "
                       f"margin above {2 * tol:.4g}")
    check(not wrong, f"{label}: tokens differ from the reference argmax "
                     f"(rid, step, engine, reference): {wrong[:8]}")


def check_quant_attention(eng, cfg, seed):
    """The quantized-KV kernels against their jnp references, on the
    engine's own (layer 0) pools at the served shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import dispatch

    pool = eng.caches[0]["pos0"]
    kw, vw, kcb, vcb = (x[0] for x in (pool.k_words, pool.v_words,
                                       pool.k_cb, pool.v_cb))
    npg, n_pages = eng.pool.max_pages_per_slot, eng.pool.n_pages
    table = jnp.asarray(1 + np.arange(N_SLOTS * npg).reshape(N_SLOTS, npg)
                        % n_pages, jnp.int32)
    pos = jnp.asarray(npg * PAGE - 1 - 37 * np.arange(N_SLOTS), jnp.int32)
    alive = jnp.ones((N_SLOTS,), bool)
    hd, bits = cfg.head_dim, eng.kv_bits
    scale = hd ** -0.5
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (N_SLOTS, 1, cfg.n_heads, hd), jnp.float32)
    kernel = dispatch.default_backend()    # "pallas" on the chip
    errs = {}
    outs = [dispatch.paged_attention_quant(
        q, kw, vw, kcb, vcb, table, pos, alive, bits=bits, head_dim=hd,
        scale=scale, backend=b) for b in (kernel, "ref")]
    errs["paged_attention_quant"] = outs
    row, one = table[:1], jnp.ones((1,), bool)
    kview = dispatch.page_gather(kw, row, one, backend="ref")
    vview = dispatch.page_gather(vw, row, one, backend="ref")
    s = kview.shape[1]
    c = 64
    qb = jax.random.normal(jax.random.fold_in(key, 1),
                           (1, c, cfg.n_heads, hd), jnp.float32)
    outs = [dispatch.blockwise_prefill_attention_quant(
        qb, kview, vview, kcb[row], vcb[row], jnp.arange(s - c, s),
        jnp.arange(s), page_size=PAGE, bits=bits, head_dim=hd, scale=scale,
        backend=b) for b in (kernel, "ref")]
    errs["blockwise_prefill_quant"] = outs
    for name, (got, want) in errs.items():
        got, want = np.asarray(got), np.asarray(want)
        err = float(np.max(np.abs(got - want)))
        bound = ATTN_TOL_REL * float(np.max(np.abs(want)))
        log(f"{name} (kv_bits={bits}, engine pools) vs jnp reference: max "
            f"|diff| {err:.4g}, bound {bound:.4g} ({ATTN_TOL_REL:g} x max "
            f"|reference|)")
        check(np.all(np.isfinite(got)) and err <= bound,
              f"{name}: Mosaic kernel off its jnp reference by {err:.4g}")


def route_report():
    """Backend, interpret-mode calls and jnp fallbacks seen so far."""
    from repro.kernels import dispatch, ops

    backend = dispatch.default_backend()
    calls = dict(ops.CALLS)
    falls = {k: dispatch.FALLBACKS.get(k, 0)
             for k in ("dequant_dot", "row_gather")}
    log(f"kernel backend {backend}; kernel calls traced: "
        f"{calls.get('mosaic', 0)} Mosaic, {calls.get('interpret', 0)} "
        f"interpret; jnp routes taken: {falls['dequant_dot']} "
        f"dequant-then-dot, {falls['row_gather']} word-row gathers")
    check(backend == "pallas", f"kernel backend is {backend!r}, not "
                               f"'pallas'")
    check(calls.get("interpret", 0) == 0, "a kernel ran in interpret mode")
    check(calls.get("mosaic", 0) > 0, "no Mosaic kernel was called")
    check(falls["dequant_dot"] == 0,
          "a packed weight took the dequant-then-dot route")


def one_chip(args, phases, jax):
    import numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import init_params

    cfg = get_config(ARCH)
    key = jax.random.PRNGKey(args.seed)
    with phases("build"):
        params = init_params(key, cfg)
        k16 = build_artifact("adaptive:16", params, jax.random.fold_in(key, 1))
        k2 = build_artifact("adaptive:2", params, jax.random.fold_in(key, 2))
        del params
        t0 = time.perf_counter()
        serving16 = k16.serving_params(packed=True)
        serving2 = k2.serving_params(packed=True)
        jax.block_until_ready((serving16, serving2))
        log(f"serving_params (both): {time.perf_counter() - t0:.1f} s")
    reqs = make_requests(cfg, args.seed)
    log(f"requests: {N_REQUESTS}, prompt lengths "
        f"{[int(r.prompt.size) for r in reqs]}, {GEN} new tokens each")
    mesh = make_mesh((1, 1), ("data", "model"),
                     devices=jax.devices()[:1])
    with phases("serve K=16 dense KV"):
        outs, _ = serve(serving16, cfg, reqs, mesh, kv_bits=0)
    with phases("f32 reference check"):
        reference_check(k16.decode(), serving16, cfg, reqs, outs, "K=16")
    with phases("serve K=2 4-bit KV"):
        outs2, eng2 = serve(serving2, cfg, reqs, mesh, kv_bits=4)
    with phases("quantized-KV kernel check"):
        check_quant_attention(eng2, cfg, args.seed)
    same = sum(int(np.sum(outs[r.rid] == outs2[r.rid])) for r in reqs)
    log(f"K=2 + 4-bit KV streams share {same}/{N_REQUESTS * GEN} tokens "
        f"with the K=16 streams (information only)")


def cstep_check(params, state, plan, qspec, mesh):
    """One warm C step, shard-local over ``model`` on ``mesh``, against
    the unsharded C step from the same LC state (both replicated on
    ``mesh``; only the codebooks of each result are kept)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import lc as lc_mod
    from repro.dist.cstep import lc_c_step_sharded

    cb_loc = lc_mod.c_step(params, state, plan.scheme, qspec,
                           plan.lc).theta
    cb_sh = lc_c_step_sharded(params, state, scheme=plan.scheme,
                              qspec=qspec, config=plan.lc, mesh=mesh,
                              axis="model").theta
    assign = jax.vmap(plan.scheme.assignments)

    @jax.jit
    def counts(w, lam, cl, cs, tol):
        ws = (w - lam / jnp.maximum(state.mu, 1e-30)).reshape(cl.shape[0],
                                                             -1)
        a_l = assign(ws, {"codebook": cl})
        d = a_l != assign(ws, {"codebook": cs})
        # distance to the nearer edge of the weight's own Voronoi cell
        mids = 0.5 * (cl[:, 1:] + cl[:, :-1])
        k = cl.shape[1]
        lo = jnp.take_along_axis(mids, jnp.clip(a_l - 1, 0, k - 2), axis=1)
        hi = jnp.take_along_axis(mids, jnp.clip(a_l, 0, k - 2), axis=1)
        edge = jnp.minimum(jnp.abs(ws - lo), jnp.abs(ws - hi))
        return jnp.sum(d), jnp.sum(d & (edge > tol)), ws.size

    leaves = {jax.tree_util.keystr(p): (w, lam) for (p, w), lam in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree_util.tree_leaves(state.lam))}
    cb_err, n_diff, n_firm, n_all = 0.0, 0, 0, 0
    for path, th in cb_loc.items():
        cl = jnp.atleast_2d(th["codebook"])                 # [G, K]
        cs = jnp.atleast_2d(cb_sh[path]["codebook"])
        scale = float(jnp.max(jnp.abs(cl)))
        cb_err = max(cb_err, float(jnp.max(jnp.abs(cl - cs))) / scale)
        nd, nf, n = counts(*leaves[path], cl, cs, CB_TOL_REL * scale)
        n_diff, n_firm, n_all = n_diff + int(nd), n_firm + int(nf), n_all + n
    log(f"sharded C step vs unsharded: codebooks max rel diff "
        f"{cb_err:.3g} (tolerance {CB_TOL_REL:g}); assignments differ for "
        f"{n_diff} of {n_all} weights, {n_firm} of them farther than the "
        f"codebook tolerance from a cell edge")
    check(np.isfinite(cb_err) and cb_err <= CB_TOL_REL,
          f"sharded C step codebooks off by {cb_err:.3g}")
    check(n_firm == 0, f"{n_firm} assignments differ away from a cell edge")


def four_chips(args, phases, jax):
    import dataclasses

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import init_params

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, JAX sees "
                          f"{len(devs)}")
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, stacks=tuple(
        dataclasses.replace(st, groups=FOUR_CHIP_LAYERS)
        for st in full.stacks))
    log(f"model: {ARCH} at full width, {cfg.n_layers} of {full.n_layers} "
        f"layers")
    key = jax.random.PRNGKey(args.seed)
    with phases("build"):
        params = init_params(key, cfg)
        k16, plan, qspec, state = build_artifact(
            "adaptive:16", params, jax.random.fold_in(key, 1),
            keep_state=True)
    with phases("C step: sharded (data=2, model=2) vs unsharded"):
        mesh = make_mesh((2, 2), ("data", "model"), devices=devs)
        params, state = jax.device_put((params, state),
                                       NamedSharding(mesh, P()))
        cstep_check(params, state, plan, qspec, mesh)
    del params, state
    serving16 = k16.serving_params(packed=True)
    reqs = make_requests(cfg, args.seed)
    one = make_mesh((1, 1), ("data", "model"), devices=devs[:1])
    tp = make_mesh((1, 4), ("data", "model"), devices=devs)
    with phases("serve K=16 one chip"):
        outs1, _ = serve(serving16, cfg, reqs, one, kv_bits=0)
    with phases("serve K=16 (data=1, model=4)"):
        outs4, _ = serve(serving16, cfg, reqs, tp, kv_bits=0)
    with phases("f32 reference check"):
        reference_check(k16.decode(), serving16, cfg, reqs, outs4,
                        "K=16 model=4")
    differ = sum(int(np.sum(outs1[r.rid] != outs4[r.rid])) for r in reqs)
    log(f"tensor-parallel streams differ from the one-chip streams in "
        f"{differ}/{N_REQUESTS * GEN} tokens")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh paths, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"chip_smoke: no repro package under {src}")
    sys.path.insert(0, src)
    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    import jax

    try:
        devs = jax.devices()
        check(devs[0].platform == "tpu",
              f"JAX found no TPU (platform {devs[0].platform!r})")
        dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs)}
        log(f"device: {dev}; jax {jax.__version__}; compile cache {cache}")
        phases = Phases(jax)
        (four_chips if args.four_chips else one_chip)(args, phases, jax)
        route_report()
        total = sum(w for _, w, _ in phases.rows)
        comp = sum(c for _, _, c in phases.rows)
        log(f"total: {total:.1f} s wall, {comp:.1f} s of it compiling")
    except SmokeError as e:
        print(f"chip_smoke FAILED: {e}", flush=True)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
