"""Production training launcher: mesh + sharded LC training + supervision.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --reduced --mesh 2x2 --steps 40 --lc

On a real TPU slice the same entry point runs with the production mesh
(--mesh 16x16 / 2x16x16 after jax.distributed.initialize); in this
container use --reduced with a small mesh via
XLA_FLAGS=--xla_force_host_platform_device_count=N (set by --host-devices
N *before* jax import).

What it wires together:
  * make_mesh + param/batch sharding rules (repro.dist.sharding)
  * activation-sharding policy (repro.models.sharding_ctx)
  * LC trainer (L steps jitted on the mesh; C steps psum-exact)
  * checkpoint/restart supervision with the LC state included
  * optional int8 gradient compression on the pod axis (--compress-grads)
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

from repro.configs import get_config, list_archs, reduce_config  # noqa: E402
from repro.core import CompressionPlan, LCConfig                 # noqa: E402
from repro.data.pipeline import LMTokenPipeline, shard_batch     # noqa: E402
from repro.dist import sharding as shard_rules                   # noqa: E402
from repro.launch.compile_cache import setup_compile_cache        # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_from_spec  # noqa: E402
from repro.models import sharding_ctx                            # noqa: E402
from repro.models.transformer import init_params, loss_fn        # noqa: E402
from repro.train import checkpoint as ckpt                       # noqa: E402
from repro.train.trainer import (LCTrainer, TrainerConfig)       # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x2 or 2x2x2; default: (1, n) over the "
                         "devices that exist")
    ap.add_argument("--host-devices", type=int, default=None)
    ap.add_argument("--multi-pod", action="store_true",
                    help="the 2x16x16 production mesh")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--lc", action="store_true", help="enable LC quantization")
    ap.add_argument("--scheme", default=None,
                    help="scheme spec (default adaptive:<k>), e.g. ternary_scale")
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--lc-iters", type=int, default=5)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--zero", action="store_true")
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    mesh = (make_production_mesh(multi_pod=True) if args.multi_pod
            else mesh_from_spec(args.mesh))
    print(f"mesh: {dict(mesh.shape)}; model: {cfg.name}")

    sharding_ctx.set_policy(sharding_ctx.Policy(mesh, mode="tp"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    p_shard = shard_rules.param_shardings(params, mesh, zero=args.zero)
    params = jax.tree_util.tree_map(jax.device_put, params, p_shard)

    pipe = LMTokenPipeline(seed=0, batch=args.batch, seq_len=args.seq,
                           vocab=cfg.vocab)

    def loss(p, batch):
        return loss_fn(p, cfg, batch)

    def batches():
        while True:
            yield shard_batch(pipe.next(), mesh)

    with mesh:
        if args.lc:
            plan = CompressionPlan.parse(
                args.scheme or f"adaptive:{args.k}",
                lc=LCConfig(mu0=1e-2, mu_growth=1.4,
                            num_lc_iters=args.lc_iters))
            tr = LCTrainer.from_plan(
                loss, plan, params,
                TrainerConfig(optimizer="adamw", lr=2e-3,
                              steps_per_l=max(1, args.steps // args.lc_iters)))
            state = tr.init(jax.random.PRNGKey(1), params)
            state = tr.run(state, batches(), log_every=1)
            ckpt.save_checkpoint(args.ckpt_dir, int(state.step), state,
                                 extra={"data_step": pipe.state.step})
            packed = plan.pack(state.params, state.lc_state, tr.qspec)
            art = os.path.join(args.ckpt_dir, "packed")
            packed.save(art)
            s = packed.summary()
            print(f"LC training done; checkpoint in {args.ckpt_dir}; "
                  f"PackedModel artifact in {art} "
                  f"(×{s['ratio']:.1f}, {s['packed_bytes']} B) — serve with "
                  f"launch.serve --packed")
        else:
            from repro.train.trainer import init_train_state, make_train_step
            tc = TrainerConfig(optimizer="adamw", lr=2e-3)
            state = init_train_state(params, tc)
            step = jax.jit(make_train_step(loss, tc))
            it = batches()
            for i in range(args.steps):
                state, m = step(state, next(it))
                if i % 10 == 0:
                    print(f"[{i:4d}] loss={float(m['loss']):.4f}")
            ckpt.save_checkpoint(args.ckpt_dir, args.steps, state,
                                 extra={"data_step": pipe.state.step})
            print("done; checkpoint saved to", args.ckpt_dir)


if __name__ == "__main__":
    main()
