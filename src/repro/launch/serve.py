"""Serving launcher: a thin client of the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --reduced --host-devices 4 --mesh 2x2 --requests 8 --slots 4

By default requests flow through ``repro.engine.Engine``: a request
queue feeding a fixed set of batch slots, a paged KV cache (fixed-size
pages + per-slot page table, finished requests' pages immediately
reusable), chunked prefill mixed with decode under a per-step token
budget, and per-slot sampling (greedy, or ``--temperature``/``--top-k``
with per-request seeds).  ``--no-engine`` restores the pre-engine
one-shot path — one fixed batch, lockstep prefill, greedy decode until
the longest request finishes (``repro.engine.oneshot``, the engine's
differential oracle).

``--packed <dir>`` serves straight from a PackedModel artifact (the
output of ``launch.train --lc`` / ``CompressionPlan.pack``): **every**
quantized leaf — attention q/k/v/o, embedding table / LM head, MoE
experts, SSM/RG-LRU projections, MLP — stays quantized in HBM and routes
through ``repro.models.qleaf`` → ``repro.kernels.dispatch`` (Mosaic
codebook-matmul / dequant-on-gather on TPU, jnp reference on CPU).
``--serve-layout packed`` (default) keeps the bit-packed uint32 word
operand HBM-resident (bits_per_index(K)/8 bytes/weight — the eq.-14
footprint); ``--serve-layout uint8`` is the legacy 1 B/weight oracle;
``--serve-leaves mlp`` restricts coverage to the pre-qleaf MLP-only
set.  The freed weight HBM is what the engine turns into serving
capacity: more slots × longer pages on the same device (see README
"Serving engine" for the sizing math).
"""
import argparse
import os
import sys


def _preparse_devices():
    if "--host-devices" in sys.argv:
        i = sys.argv.index("--host-devices")
        n = int(sys.argv[i + 1])
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={n}")


_preparse_devices()

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
import numpy as np                            # noqa: E402

from repro.configs import get_config, list_archs, reduce_config  # noqa: E402
from repro.dist import sharding as shard_rules                   # noqa: E402
from repro.engine import Engine, Request, greedy_generate        # noqa: E402
from repro.engine.outcomes import Outcome                         # noqa: E402
from repro.launch.compile_cache import setup_compile_cache        # noqa: E402
from repro.launch.mesh import mesh_from_spec                      # noqa: E402
from repro.models import sharding_ctx                            # noqa: E402
from repro.models.transformer import init_params                 # noqa: E402
from repro.train import checkpoint as ckpt                       # noqa: E402


def _load_params(args, cfg):
    if args.packed:
        from repro.core import ArtifactError, PackedModel
        try:
            packed = PackedModel.load(args.packed)
        except ArtifactError as e:
            # integrity gate: a truncated/corrupt artifact must fail the
            # launch cleanly, never half-serve
            sys.exit(f"refusing to serve {args.packed}: {e}")
        quant_names = (None if args.serve_leaves == "all"
                       else ("w_in", "w_gate", "w_out"))
        params = packed.serving_params(
            quant_names=quant_names, packed=args.serve_layout == "packed")
        s = packed.summary()
        idx_bytes = (s["bits_per_weight"] / 8
                     if args.serve_layout == "packed" else 1.0)
        cov = packed.leaf_coverage()
        n_q = sum(r["quantized"] for r in cov)
        # row-packed fused routes only exist on the bit-packed layout
        # with full coverage (uint8/MLP-only serving never emits them)
        n_row = (sum(r["quantized"] and "pack_rows" in (r["route"] or "")
                     for r in cov)
                 if args.serve_layout == "packed"
                 and args.serve_leaves == "all" else 0)
        row_note = (f", {n_row} row-packed for fused gather + transposed "
                    f"head" if n_row else "")
        print(f"serving packed artifact: {s['scheme']} "
              f"({s['bits_per_weight']} bit/weight, ×{s['ratio']:.1f}, "
              f"{args.serve_layout} layout: {idx_bytes:g} B/weight HBM "
              f"index traffic; {args.serve_leaves} leaves — "
              f"{n_q}/{len(cov)} param paths quantized{row_note})")
        return params
    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        params, _, _ = ckpt.restore_checkpoint(args.ckpt_dir, like=params)
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x2 (data x model); default: (1, n) over "
                         "the devices that exist")
    ap.add_argument("--host-devices", type=int, default=None)
    ap.add_argument("--batch", type=int, default=4,
                    help="one-shot batch size / engine slot count alias")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--packed", default=None,
                    help="PackedModel artifact dir: serve quantized")
    ap.add_argument("--serve-layout", default="packed",
                    choices=("packed", "uint8"),
                    help="quantized HBM layout: bit-packed uint32 words "
                         "(bits/8 B/weight) or legacy uint8 indices "
                         "(1 B/weight oracle)")
    ap.add_argument("--serve-leaves", default="all", choices=("all", "mlp"),
                    help="which leaves serve quantized: the whole model "
                         "(attention/embed/MoE/SSM/MLP) or the legacy "
                         "MLP-only set")
    # engine knobs
    ap.add_argument("--no-engine", action="store_true",
                    help="one-shot lockstep loop (the engine's oracle)")
    ap.add_argument("--requests", type=int, default=None,
                    help="number of requests (default: --batch)")
    ap.add_argument("--slots", type=int, default=None,
                    help="engine batch slots (default: --batch)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size in tokens")
    ap.add_argument("--pages", type=int, default=None,
                    help="page-pool size (default: slots × max pages)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-step token budget (decode + chunked prefill)")
    ap.add_argument("--kv-bits", type=int, default=0,
                    choices=(0, 2, 4, 8),
                    help="codebook-quantize KV pages to this many bits "
                         "(0 = dense pages); kv_bits/8 B per cached "
                         "scalar of decode HBM traffic")
    ap.add_argument("--kv-cb", default="page", choices=("page", "head"),
                    help="KV codebook grouping: one per page, or one per "
                         "(page, kv-head) — finer fit, n_kv× metadata")
    ap.add_argument("--vary-gen", action="store_true",
                    help="stagger request gen lengths (engine mode)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    # fault tolerance (engine mode)
    ap.add_argument("--deadline", type=int, default=None,
                    help="per-request deadline in engine steps "
                         "(DEADLINE_EXCEEDED past it)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound the request queue; submissions beyond it "
                         "get REJECTED_BACKPRESSURE")
    ap.add_argument("--snapshot-dir", default=None,
                    help="serve under the restart supervisor with "
                         "periodic snapshots to this directory")
    ap.add_argument("--snapshot-every", type=int, default=32,
                    help="steps between snapshots (with --snapshot-dir)")
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    mesh = mesh_from_spec(args.mesh)
    sharding_ctx.set_policy(sharding_ctx.Policy(mesh, mode="tp"))

    params = _load_params(args, cfg)
    p_shard = shard_rules.param_shardings(params, mesh)
    params = jax.tree_util.tree_map(jax.device_put, params, p_shard)

    key = jax.random.PRNGKey(7)
    n_req = args.requests if args.requests is not None else args.batch
    prompts = jax.random.randint(key, (n_req, args.prompt_len), 0,
                                 cfg.vocab)

    if args.no_engine:
        n_b = min(args.batch, n_req)
        with mesh:
            gen, _ = greedy_generate(params, cfg, prompts[:n_b],
                                     args.gen_len)
        for r in range(n_b):
            print(f"req{r}: {np.asarray(gen)[r]}")
        return

    n_slots = args.slots if args.slots is not None else args.batch
    rng = np.random.RandomState(args.seed)
    reqs = []
    for r in range(n_req):
        gen_len = (int(rng.randint(max(args.gen_len // 4, 1),
                                   args.gen_len + 1))
                   if args.vary_gen else args.gen_len)
        reqs.append(Request(rid=r, prompt=np.asarray(prompts[r]),
                            max_new_tokens=gen_len,
                            temperature=args.temperature,
                            top_k=args.top_k, seed=args.seed + r,
                            deadline_steps=args.deadline))

    def build():
        return Engine(params, cfg, n_slots=n_slots,
                      page_size=args.page_size,
                      max_seq=args.prompt_len + args.gen_len,
                      n_pages=args.pages, token_budget=args.token_budget,
                      mesh=mesh, queue_limit=args.queue_limit,
                      kv_bits=args.kv_bits, kv_cb_mode=args.kv_cb)

    with mesh:
        if args.snapshot_dir:
            from repro.engine import (ServeSupervisorConfig,
                                      supervised_serve)
            sup = ServeSupervisorConfig(snapshot_dir=args.snapshot_dir,
                                        snapshot_every=args.snapshot_every)
            outs, results, report = supervised_serve(build, reqs, sup)
            eng = None
            print(f"supervisor: {report.snapshots} snapshots, "
                  f"{report.restores} restores, {report.restarts} restarts")
        else:
            eng = build()
            outs = eng.run(reqs)
            results = eng.results
    for r in sorted(results):
        res = results[r]
        if res.ok:
            print(f"req{r}: {res.tokens}")
        else:
            print(f"req{r}: {res.outcome.value} ({res.detail}; "
                  f"{res.tokens.size} partial tokens)")
    n_bad = sum(not res.ok for res in results.values())
    if n_bad:
        print(f"outcomes: {len(results) - n_bad}/{len(results)} finished")
    n_failed = sum(res.outcome is Outcome.FAILED
                   for res in results.values())
    if eng is not None:
        s = eng.stats.summary()
        print(f"engine: {s['delivered_tokens']} tokens in {s['steps']} "
              f"steps ({s['tokens_per_s']:.1f} tok/s, occupancy "
              f"{s['slot_occupancy']:.2f}, page util "
              f"{s['page_utilization']:.2f}"
              f" peak {s['page_utilization_max']:.2f}, "
              f"{s['preemptions']} preemptions, decode compiled "
              f"{eng.decode_compile_count()}x)")
    if n_failed:
        # rejections and deadlines are outcomes the user configured; a
        # FAILED request (non-finite logits, exhausted preemption budget,
        # max_steps overrun) is the server failing
        sys.exit(f"{n_failed} request(s) FAILED")


if __name__ == "__main__":
    main()
