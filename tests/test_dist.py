"""Distributed semantics, run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests in this process
keep seeing 1 device, per the dry-run isolation rule).

Covers: shard_map'd k-means == single-device k-means; histogram
ternary-scale == exact sort solution; int8-compressed psum accuracy;
elastic checkpoint reshard (save on 8-dev mesh, load on 4); sharding-rule
divisibility validation."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str) -> dict:
    """Run ``body`` in a subprocess with 8 host devices; it must print a
    JSON dict on the last line."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_kmeans_equals_single_device():
    res = run_sub("""
        from repro.dist.cstep import sharded_kmeans
        from repro.core.kmeans import kmeans_fit, quantile_init
        mesh = make_mesh((2, 4), ("data", "model"))
        w = jax.random.normal(jax.random.PRNGKey(0), (4096,))
        cb0 = quantile_init(w, 4)
        cb_d, assign_d, dist_d = sharded_kmeans(w, cb0, mesh, iters=20,
                                                axis="model")
        res_s = kmeans_fit(w, cb0, iters=20)
        print(json.dumps({
            "cb_close": bool(np.allclose(np.asarray(cb_d),
                                         np.asarray(res_s.codebook),
                                         rtol=1e-5, atol=1e-6)),
            "dist_close": bool(np.isclose(float(dist_d),
                                          float(res_s.distortion),
                                          rtol=1e-5)),
        }))
    """)
    assert res["cb_close"] and res["dist_close"]


def test_histogram_warm_start_first_adaptive_cstep_1dev():
    """sharded_c_step(codebook=None) — the first-C-step histogram-quantile
    warm start (ROADMAP distributed item).  On a 1-device mesh it must
    equal the identical local pipeline (histogram-quantile init + k-means,
    psum over one shard is the identity) bit-for-bit, and land on the
    same solution as the local k-means++-init first C step."""
    res = run_sub("""
        from functools import partial
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist.cstep import histogram_quantiles, sharded_c_step
        from repro.core.kmeans import kmeans_fit, kmeans_plus_plus_init
        from repro.core.schemes import make_scheme
        scheme = make_scheme("adaptive:4")
        mesh = make_mesh((1,), ("model",))
        w = jax.random.normal(jax.random.PRNGKey(0), (8192,))
        @partial(shard_map, mesh=mesh, in_specs=(P("model"),),
                 out_specs=(P("model"), P()), check_rep=False)
        def first_c(ws):
            q, th = sharded_c_step(scheme, ws, "model")   # no codebook
            return q, th["codebook"]
        q_d, cb_d = first_c(w)
        # identical local pipeline (axis_name=None): exact equality
        cb0 = histogram_quantiles(w, 4, None)
        res_l = kmeans_fit(w, cb0, iters=scheme.iters_first)
        q_l = res_l.codebook[res_l.assignments]
        # local k-means++ init first C step: same converged solution
        cbpp = kmeans_plus_plus_init(jax.random.PRNGKey(1), w, 4)
        res_pp = kmeans_fit(w, cbpp, iters=scheme.iters_first)
        print(json.dumps({
            "q_equal": bool(np.array_equal(np.asarray(q_d),
                                           np.asarray(q_l))),
            "cb_equal": bool(np.array_equal(np.asarray(cb_d),
                                            np.asarray(res_l.codebook))),
            "cb_vs_pp": bool(np.allclose(np.asarray(cb_d),
                                         np.asarray(res_pp.codebook),
                                         atol=5e-2)),
            "dist_vs_pp": abs(float(res_l.distortion)
                              - float(res_pp.distortion))
                          / float(res_pp.distortion),
        }))
    """)
    assert res["q_equal"] and res["cb_equal"]
    assert res["cb_vs_pp"]
    assert res["dist_vs_pp"] < 2e-2


def test_histogram_ternary_scale_matches_exact():
    res = run_sub("""
        from functools import partial
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist.cstep import ternary_scale_histogram
        from repro.core.quant_ops import ternarize_scale
        mesh = make_mesh((8,), ("model",))
        w = jax.random.normal(jax.random.PRNGKey(1), (8192,))
        @partial(shard_map, mesh=mesh, in_specs=P("model"),
                 out_specs=P(None), check_rep=False)
        def dist_scale(ws):
            return ternary_scale_histogram(ws, "model")[None]
        a_d = float(dist_scale(w)[0])
        _, a_exact = ternarize_scale(w)
        print(json.dumps({"a_d": a_d, "a_exact": float(a_exact)}))
    """)
    assert res["a_d"] == pytest.approx(res["a_exact"], rel=2e-3)


def test_compressed_psum_accuracy():
    res = run_sub("""
        from functools import partial
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist.cstep import compressed_psum
        mesh = make_mesh((8,), ("pod",))
        g = jax.random.normal(jax.random.PRNGKey(2), (8, 4096)) \
            * jnp.logspace(-2, 0, 8)[:, None]   # heterogeneous scales
        @partial(shard_map, mesh=mesh, in_specs=P("pod", None),
                 out_specs=P("pod", None), check_rep=False)
        def comp(x):
            return compressed_psum(x[0], "pod")[None]
        @partial(shard_map, mesh=mesh, in_specs=P("pod", None),
                 out_specs=P("pod", None), check_rep=False)
        def exact(x):
            return jax.lax.psum(x[0], "pod")[None]
        c = np.asarray(comp(g))[0]
        e = np.asarray(exact(g))[0]
        rel = float(np.linalg.norm(c - e) / np.linalg.norm(e))
        print(json.dumps({"rel_err": rel}))
    """)
    assert res["rel_err"] < 0.02


def test_elastic_checkpoint_reshard():
    res = run_sub("""
        import tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train import checkpoint as ckpt
        tmp = tempfile.mkdtemp()
        mesh8 = make_mesh((2, 4), ("data", "model"))
        x = jnp.arange(64.0).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh8, P(None, "model")))
        ckpt.save_checkpoint(tmp, 1, {"w": xs})
        mesh4 = make_mesh((2, 2), ("data", "model"))
        sh = {"w": NamedSharding(mesh4, P("model", None))}
        out, _, _ = ckpt.restore_checkpoint(tmp, like={"w": x}, shardings=sh)
        ok = bool(np.allclose(np.asarray(out["w"]), np.asarray(x)))
        nshards = len(out["w"].sharding.device_set)
        print(json.dumps({"ok": ok, "nshards": nshards}))
    """)
    assert res["ok"] and res["nshards"] == 4


def test_param_sharding_rules_divisibility():
    res = run_sub("""
        from repro.dist.sharding import param_shardings
        mesh = make_mesh((2, 4), ("data", "model"))
        params = {
            "embed_tok": jnp.zeros((50281, 64)),      # 50281 % 4 != 0
            "stacks": ({"pos0": {"mixer": {"wq": jnp.zeros((2, 64, 64))},
                                 "mlp": {"w_in": jnp.zeros((2, 64, 128))}}},),
        }
        sh = param_shardings(params, mesh)
        emb = sh["embed_tok"].spec
        wq = sh["stacks"][0]["pos0"]["mixer"]["wq"].spec
        w_in = sh["stacks"][0]["pos0"]["mlp"]["w_in"].spec
        print(json.dumps({"emb": str(emb), "wq": str(wq),
                          "w_in": str(w_in)}))
    """)
    assert "model" not in res["emb"]               # dropped: not divisible
    assert res["wq"] == "PartitionSpec(None, None, 'model')"
    assert res["w_in"] == "PartitionSpec(None, None, 'model')"


def test_debug_mesh_dryrun_tiny():
    """End-to-end mini dry-run on an 8-device (2,2,2) multi-pod mesh:
    lower+compile the reduced qwen train step with production shardings."""
    res = run_sub("""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduce_config
        from repro.dist import sharding as shard_rules
        from repro.launch.mesh import make_debug_mesh
        from repro.models import sharding_ctx
        from repro.models import transformer as tfm
        mesh = make_debug_mesh(2, 2, pods=2)
        cfg = reduce_config(get_config("qwen1.5-0.5b"))
        sharding_ctx.set_policy(sharding_ctx.Policy(mesh, mode="tp"))
        params_sh = jax.eval_shape(
            lambda k: tfm.init_params(k, cfg, jnp.bfloat16),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        p_shard = shard_rules.param_shardings(params_sh, mesh)
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        b_shard = shard_rules.batch_shardings(batch, mesh)
        def loss(p, b):
            return tfm.loss_fn(p, cfg, b)
        with mesh:
            compiled = jax.jit(loss, in_shardings=(p_shard, b_shard),
                               out_shardings=NamedSharding(mesh, P())
                               ).lower(params_sh, batch).compile()
        mem = compiled.memory_analysis()
        # older jaxlibs lack peak_memory_in_bytes (dryrun.py guards it too)
        peak = getattr(mem, "peak_memory_in_bytes", None) or (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
        print(json.dumps({"ok": True, "peak": int(peak)}))
    """)
    assert res["ok"] and res["peak"] > 0


def test_lc_c_step_sharded_equals_local_8dev():
    """ROADMAP distributed item: the plan-driven shard-local C step
    (repro.dist.cstep.lc_c_step_sharded) must walk the same (w_C, Θ)
    trajectory as repro.core.lc.c_step — adaptive k-means statistics are
    psum-exact, so grouped and flat leaves both match to fp tolerance."""
    res = run_sub("""
        from repro.core import lc as lc_mod
        from repro.core.schemes import make_scheme
        from repro.dist.cstep import lc_c_step_sharded
        mesh = make_mesh((8,), ("model",))
        scheme = make_scheme("adaptive:4")
        key = jax.random.PRNGKey(0)
        params = {
            "w": jax.random.normal(key, (64, 64)),            # flat leaf
            "stack_w": jax.random.normal(key, (2, 32, 64)),   # grouped leaf
            "tail": jax.random.normal(key, (3, 19)),          # 57 % 8 != 0
        }
        qspec = lc_mod.default_qspec(params)
        cfg = lc_mod.LCConfig(mu0=1e-2, mu_growth=1.5)
        state = lc_mod.lc_init(key, params, scheme, qspec, cfg)
        loc = lc_mod.c_step(params, state, scheme, qspec, cfg)
        sh = lc_c_step_sharded(params, state, scheme=scheme, qspec=qspec,
                               config=cfg, mesh=mesh, axis="model")
        flat_ok = all(
            np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
            for a, b in zip(jax.tree_util.tree_leaves(loc.w_c),
                            jax.tree_util.tree_leaves(sh.w_c)))
        cb_ok = all(
            np.allclose(np.asarray(loc.theta[p]["codebook"]),
                        np.asarray(sh.theta[p]["codebook"]),
                        rtol=1e-5, atol=1e-6)
            for p in loc.theta)
        print(json.dumps({"w_c": flat_ok, "cb": cb_ok,
                          "mu": float(sh.mu) == float(loc.mu)}))
    """)
    assert res["w_c"] and res["cb"] and res["mu"]


def test_adaptive_zero_sharded_c_step_8dev():
    """PR-4 distributed item: adaptive_zero's pinned-zero centroid step
    has a sharded primitive (adaptive_zero_kmeans_psum) — the plan-driven
    shard-local C step walks the same (w_C, Θ) trajectory as the local
    solver on an 8-device mesh, the zero centroid stays pinned exactly,
    and the remaining fallback boundary is only divisibility (the 'tail'
    leaf, 57 % 8 != 0, takes the local path and still matches)."""
    res = run_sub("""
        from functools import partial
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core import lc as lc_mod
        from repro.core.schemes import make_scheme
        from repro.dist.cstep import (histogram_quantiles, lc_c_step_sharded,
                                      sharded_c_step)
        mesh = make_mesh((8,), ("model",))
        scheme = make_scheme("adaptive_zero:4")
        key = jax.random.PRNGKey(0)
        params = {
            "w": jax.random.normal(key, (64, 64)),            # divisible
            "tail": jax.random.normal(key, (3, 19)),          # 57 % 8 != 0
        }
        qspec = lc_mod.default_qspec(params)
        cfg = lc_mod.LCConfig(mu0=1e-2, mu_growth=1.5)
        state = lc_mod.lc_init(key, params, scheme, qspec, cfg)
        loc = lc_mod.c_step(params, state, scheme, qspec, cfg)
        sh = lc_c_step_sharded(params, state, scheme=scheme, qspec=qspec,
                               config=cfg, mesh=mesh, axis="model")
        w_ok = all(
            np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
            for a, b in zip(jax.tree_util.tree_leaves(loc.w_c),
                            jax.tree_util.tree_leaves(sh.w_c)))
        cb_ok = all(
            np.allclose(np.asarray(loc.theta[p]["codebook"]),
                        np.asarray(sh.theta[p]["codebook"]),
                        rtol=1e-5, atol=1e-6)
            for p in loc.theta)
        pinned = all(0.0 in np.asarray(sh.theta[p]["codebook"])
                     for p in sh.theta)
        # first-C-step path (codebook=None): histogram warm start + pin,
        # equal to the identical local pipeline on the same mesh
        w8 = jax.random.normal(jax.random.fold_in(key, 9), (8192,))
        @partial(shard_map, mesh=mesh, in_specs=(P("model"),),
                 out_specs=(P("model"), P()), check_rep=False)
        def first_c(ws):
            q, th = sharded_c_step(scheme, ws, "model")
            return q, th["codebook"]
        q_d, cb_d = first_c(w8)
        cb0 = histogram_quantiles(w8, 4, None)
        cb0 = jnp.sort(cb0.at[jnp.argmin(jnp.abs(cb0))].set(0.0))
        from repro.dist.cstep import adaptive_zero_kmeans_psum
        cb_l, q_l = adaptive_zero_kmeans_psum(w8, cb0, 4, None,
                                              scheme.iters_first)
        first_ok = bool(np.allclose(np.asarray(cb_d), np.asarray(cb_l),
                                    rtol=1e-5, atol=1e-6))
        first_pinned = bool((np.asarray(cb_d) == 0.0).any())
        print(json.dumps({"w_c": w_ok, "cb": cb_ok, "pinned": bool(pinned),
                          "first": first_ok,
                          "first_pinned": first_pinned}))
    """)
    assert res["w_c"] and res["cb"]
    assert res["pinned"], "zero centroid must stay exactly pinned"
    assert res["first"] and res["first_pinned"]


def test_lctrainer_sharded_c_step_plan_flag_1dev():
    """Smoke-test the plan flag end to end on a 1-device mesh (in-process:
    jax sees one CPU device here): CompressionPlan(sharded_c_step=True) →
    LCTrainer.from_plan(..., mesh=...) runs, and its LC trajectory matches
    the local-C-step trainer on the same data."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import CompressionPlan, LCConfig
    from repro.launch.mesh import make_mesh
    from repro.train.trainer import LCTrainer, TrainerConfig

    key = jax.random.PRNGKey(0)
    w_true = jax.random.normal(key, (8, 8))
    xs = jax.random.normal(jax.random.fold_in(key, 1), (64, 8))
    ys = xs @ w_true

    def loss(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] - y) ** 2)

    def batches():
        while True:
            yield (xs, ys)

    params = {"w": jax.random.normal(jax.random.fold_in(key, 2), (8, 8))}
    tc = TrainerConfig(lr=0.05, steps_per_l=5)
    lc = LCConfig(mu0=1e-2, mu_growth=1.5, num_lc_iters=3)
    mesh = make_mesh((1,), ("model",))

    plan_sh = CompressionPlan.parse("adaptive:4", lc=lc,
                                    sharded_c_step=True,
                                    init_method="quantile")
    plan_loc = CompressionPlan.parse("adaptive:4", lc=lc,
                                     init_method="quantile")
    tr_sh = LCTrainer.from_plan(loss, plan_sh, params, tc, mesh=mesh)
    tr_loc = LCTrainer.from_plan(loss, plan_loc, params, tc)
    st_sh = tr_sh.run(tr_sh.init(key, params), batches())
    st_loc = tr_loc.run(tr_loc.init(key, params), batches())

    q_sh = tr_sh.finalize(st_sh)
    q_loc = tr_loc.finalize(st_loc)
    np.testing.assert_allclose(np.asarray(q_sh["w"]), np.asarray(q_loc["w"]),
                               rtol=1e-5, atol=1e-6)
    cb_sh = st_sh.lc_state.theta["['w']"]["codebook"]
    cb_loc = st_loc.lc_state.theta["['w']"]["codebook"]
    np.testing.assert_allclose(np.asarray(cb_sh), np.asarray(cb_loc),
                               rtol=1e-5, atol=1e-6)


def test_engine_paged_cache_sharding_rules():
    """Page pools replicate the page axis over data (any slot's table
    entry may point at any physical page) and shard the kv-head axis
    over ``model``; per-slot state shards the slot axis over data like a
    decode batch.  A fused engine decode step must run under these
    placements on a 2×4 mesh without resharding errors."""
    res = run_sub("""
        from repro.dist.sharding import engine_cache_shardings, param_shardings
        from repro.models.transformer import (LayerKind, ModelConfig,
                                              StackSpec, decode_step_slots,
                                              init_paged_cache, init_params)
        cfg = ModelConfig(
            name="tiny", family="dense", d_model=32, n_heads=8, n_kv=4,
            head_dim=4, d_ff=64, vocab=96,
            stacks=(StackSpec(pattern=(LayerKind("gqa", "dense"),),
                              groups=2),),
            tie_embeddings=True, q_chunk=8, kv_chunk=8, remat=False)
        mesh = make_mesh((2, 4), ("data", "model"))
        n_slots, n_pages, page = 4, 8, 4
        caches = init_paged_cache(cfg, n_slots, n_pages, page)
        sh = engine_cache_shardings(caches, mesh, n_slots=n_slots,
                                    n_pages=n_pages)
        pool_sh = sh[0]["pos0"].k        # [G, n_pages+1, page, kv, hd]
        pool_spec = tuple(pool_sh.spec)
        # the ambiguous oversubscribed case (n_pages + 1 == n_slots):
        # pool pages must still replicate, never data-shard
        amb = init_paged_cache(cfg, 4, 3, page)
        amb_sh = engine_cache_shardings(amb, mesh, n_slots=4, n_pages=3)
        amb_spec = tuple(amb_sh[0]["pos0"].k.spec)
        params = init_params(jax.random.PRNGKey(0), cfg)
        params = jax.tree_util.tree_map(jax.device_put, params,
                                        param_shardings(params, mesh))
        caches = jax.tree_util.tree_map(jax.device_put, caches, sh)
        pt = jnp.zeros((n_slots, 2), jnp.int32).at[:, 0].set(
            jnp.arange(1, n_slots + 1))
        toks = jnp.zeros((n_slots, 1), jnp.int32)
        pos = jnp.zeros((n_slots,), jnp.int32)
        alive = jnp.ones((n_slots,), bool)
        with mesh:
            logits, _ = jax.jit(decode_step_slots, static_argnums=1)(
                params, cfg, caches, pt, toks, pos, alive)
        print(json.dumps({
            "pool_spec": [str(s) for s in pool_spec],
            "pool_model_axis": pool_spec[3] == "model",
            "pool_pages_replicated": pool_spec[1] is None,
            "ambiguous_pool_pages_replicated": amb_spec[1] is None,
            "logits_ok": bool(np.isfinite(np.asarray(logits)).all()),
        }))
    """)
    assert res["pool_model_axis"], res
    assert res["pool_pages_replicated"], res
    assert res["ambiguous_pool_pages_replicated"], res
    assert res["logits_ok"], res


def test_moe_ep_shard_map_equals_vmap():
    """Rank-local EP dispatch (shard_map) == the local vmap path."""
    res = run_sub("""
        from repro.models.moe import apply_moe, init_moe
        from repro.models import sharding_ctx
        key = jax.random.PRNGKey(0)
        p = init_moe(key, 16, 8, 8, 1, "silu")
        x = jax.random.normal(key, (4, 16, 16))
        y_ref = apply_moe(p, x, top_k=2, capacity_factor=4.0)
        mesh = make_mesh((2, 4), ("data", "model"))
        sharding_ctx.set_policy(sharding_ctx.Policy(mesh, mode="tp"))
        with mesh:
            y_ep = jax.jit(lambda p, x: apply_moe(p, x, top_k=2,
                                                  capacity_factor=4.0))(p, x)
        ok = bool(np.allclose(np.asarray(y_ref), np.asarray(y_ep),
                              rtol=2e-4, atol=2e-5))
        print(json.dumps({"ok": ok}))
    """)
    assert res["ok"]


def test_mesh_helper_auto_axes_and_constrain_2x4():
    """launch.mesh builds Auto-axis meshes (JAX 0.9 defaults to Explicit,
    which rejects sharding_ctx's constraints), and the activation policy's
    specs apply on a 2x4 host mesh inside jit."""
    res = run_sub("""
        from jax.sharding import AxisType
        from repro.launch.mesh import make_debug_mesh, mesh_from_spec
        from repro.models import sharding_ctx
        mesh = mesh_from_spec("2x4")
        local = mesh_from_spec(None)
        pol = sharding_ctx.Policy(mesh, mode="tp")

        @jax.jit
        def f(x):
            with sharding_ctx.activation_policy(pol):
                return sharding_ctx.constrain(x * 2, "batch", None, "heads")

        with mesh:
            y = f(jnp.ones((4, 3, 8)))
        print(json.dumps({
            "types": sorted({t.name for m in (mesh, local,
                                              make_debug_mesh(2, 2, 2))
                             for t in m.axis_types}),
            "local": [list(local.axis_names), list(local.devices.shape)],
            "spec": str(y.sharding.spec),
            "ok": bool(np.all(np.asarray(y) == 2.0)),
        }))
    """)
    assert res["types"] == ["Auto"]
    assert res["local"] == [["data", "model"], [1, 8]]
    assert res["spec"] == "PartitionSpec('data', None, 'model')"
    assert res["ok"]


def test_pallas_call_under_mesh_policy_2x4():
    """Mosaic kernels cannot be partitioned by the compiler: the model
    call sites pass the policy's mesh to dispatch, which runs the kernel
    route in a shard_map with the weight's own split (column: N over
    model; row: Kd over model + psum; other: replicated).  On sharded
    operands inside jit each result must equal the unmapped call
    (interpret mode here)."""
    res = run_sub("""
        os.environ["REPRO_KERNEL_BACKEND"] = "pallas_interpret"
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import compression as C
        from repro.kernels import dispatch
        from repro.models import qleaf, sharding_ctx
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.RandomState(0)
        idx = rng.randint(0, 16, size=(256, 512))
        cb = jnp.asarray(np.sort(rng.randn(16)), jnp.float32)
        pidx = jnp.asarray(C.pack_indices_2d(idx, 16))
        layout = C.PackedLayout.make(256, 512, 16)
        x = jnp.asarray(rng.randn(2, 4, 256), jnp.float32)
        want = np.asarray(dispatch.packed_quantized_matmul(x, pidx, cb,
                                                           layout=layout))
        placed = {"w": P(None, "model"), "wo": P("model", None)}
        out = {}
        with mesh, sharding_ctx.activation_policy(
                sharding_ctx.Policy(mesh, mode="tp")):
            for name, spec in placed.items():
                leaf = {f"{name}_pidx": jax.device_put(
                            pidx, NamedSharding(mesh, spec)),
                        f"{name}_cb": cb, f"{name}_layout": layout}
                f = jax.jit(lambda x, leaf, n=name: qleaf.qmatmul(leaf, n, x))
                out[name] = np.asarray(f(x, leaf))
            out["replicated"] = np.asarray(jax.jit(
                lambda x: dispatch.packed_quantized_matmul(
                    x, pidx, cb, layout=layout, mesh=mesh))(x))
        print(json.dumps({
            "err": {k: float(np.max(np.abs(v - want)))
                    for k, v in out.items()},
            "scale": float(np.max(np.abs(want))),
            "equal": [k for k, v in out.items()
                      if np.array_equal(v, want)]}))
    """)
    assert "replicated" in res["equal"], res
    # split operands change the kernel's block shapes, and the row split
    # sums 4 partial products: f32 reordering only
    assert max(res["err"].values()) <= 1e-5 * res["scale"], res


def test_dispatch_tensor_parallel_routes_2x4():
    """The vocabulary-split LM head and embedding gather and the
    head-split paged decode and blockwise prefill, on a (2, 4) mesh in
    interpret mode, equal their unmapped calls."""
    res = run_sub("""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import compression as C
        from repro.kernels import dispatch
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.RandomState(1)
        b = "pallas_interpret"
        V, D, K = 64, 128, 16
        idx = rng.randint(0, K, size=(V, D))
        emb = jnp.asarray(C.pack_rows(idx, K))
        lay = C.PackedLayout.make(V, D, K, order="row")
        cb = jnp.asarray(np.sort(rng.randn(K)), jnp.float32)
        x = jnp.asarray(rng.randn(3, D), jnp.float32)
        toks = jnp.asarray(rng.randint(0, V, size=(2, 5)), jnp.int32)
        H, KV, hd, page, n_pages, B = 8, 4, 16, 8, 6, 2
        kp = jnp.asarray(rng.randn(n_pages + 1, page, KV, hd), jnp.float32)
        vp = jnp.asarray(rng.randn(n_pages + 1, page, KV, hd), jnp.float32)
        q = jnp.asarray(rng.randn(B, 1, H, hd), jnp.float32)
        table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
        pos = jnp.asarray([13, 20], jnp.int32)
        alive = jnp.ones((B,), bool)
        qb = jnp.asarray(rng.randn(B, 8, H, hd), jnp.float32)
        kb = jnp.asarray(rng.randn(B, 24, KV, hd), jnp.float32)
        vb = jnp.asarray(rng.randn(B, 24, KV, hd), jnp.float32)
        calls = {
            "head": lambda m: dispatch.packed_quantized_matmul_t(
                x, emb_s if m else emb, cb, layout=lay, backend=b, mesh=m),
            "gather": lambda m: dispatch.quantized_gather(
                toks, emb_s if m else emb, cb, layout=lay, backend=b,
                mesh=m),
            "paged": lambda m: dispatch.paged_attention(
                q, kp_s if m else kp, vp_s if m else vp, table, pos, alive,
                scale=0.25, backend=b, mesh=m),
            "gather_pages": lambda m: dispatch.page_gather(
                kp_s if m else kp, table, alive, backend=b, mesh=m),
            "prefill": lambda m: dispatch.blockwise_prefill_attention(
                qb, kb, vb, jnp.arange(16, 24), jnp.arange(24), scale=0.25,
                backend=b, mesh=m),
        }
        emb_s = jax.device_put(emb, NamedSharding(mesh, P("model", None)))
        heads = NamedSharding(mesh, P(None, None, "model", None))
        kp_s, vp_s = jax.device_put(kp, heads), jax.device_put(vp, heads)
        err = {}
        for name, call in calls.items():
            want = np.asarray(call(None))
            with mesh:
                got = np.asarray(jax.jit(lambda: call(mesh))())
            err[name] = float(np.max(np.abs(got - want)))
        print(json.dumps(err))
    """)
    assert res == {k: 0.0 for k in ("head", "gather", "paged",
                                    "gather_pages", "prefill")}, res
