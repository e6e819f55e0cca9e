#!/usr/bin/env bash
# Tier-1 verify: the command CI and local dev both run (ROADMAP.md).
#
#   ./scripts/verify.sh [extra pytest args]
#
# Notes on XLA host-device flags (SNIPPETS.md): the distributed tests
# (tests/test_dist.py) spawn subprocesses that set
#   XLA_FLAGS=--xla_force_host_platform_device_count=8
# themselves — the parent process must stay single-device (the dry-run
# isolation rule: jax locks the device count at first init).  Do NOT
# export that flag here; export it only when running a multi-device
# entry point directly, e.g.:
#   XLA_FLAGS=--xla_force_host_platform_device_count=8 \
#       python -m repro.launch.train --reduced --mesh 2x4 --lc
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# XLA's CPU backend splits LLVM codegen across a thread pool; on
# low-core runners that parallel split races and sporadically SIGSEGVs
# inside backend_compile on long many-compilation runs (observed on a
# 1-vCPU box compiling the kmeans scan, different test each run).
# Serializing codegen removes the crash and costs nothing at CI scale.
# Appended so a caller's XLA_FLAGS still apply; the test_dist.py
# subprocesses overwrite XLA_FLAGS themselves (see note above) and are
# single-compile, short-lived processes.
export XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_cpu_parallel_codegen_split_count=1"

# Differential matrices first — the serving-layout invariant ({dense,
# uint8, packed} × {forward, prefill, decode} × K × dtype bit-exact;
# tests/test_differential.py + golden artifacts) and the paged-KV
# invariant ({dense KV, quantized KV} × {gqa, mla} × K: quant refs ==
# dense refs on dequantized pools bit-exactly, engine streams == the
# one-shot oracle at kv_bits=0; tests/test_paged_attention.py) — both
# before any engine smoke below, so a KV-cache regression fails the
# build at the kernel oracle, not in an end-to-end stream diff.  Then
# the rest of tier-1.  With extra pytest args, fall back to one plain
# invocation so -k/--lf/-m filters keep applying to everything.
# tests/test_tpu_compile.py compiles the served kernels for a described
# TPU v5e (libtpu, no chip); it skips where libtpu cannot describe one.
if [ "$#" -gt 0 ]; then
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
else
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q \
        tests/test_differential.py tests/test_golden_fixtures.py \
        tests/test_paged_attention.py
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q \
        --ignore=tests/test_differential.py \
        --ignore=tests/test_golden_fixtures.py \
        --ignore=tests/test_paged_attention.py
fi

# Full-model packed-serving smoke: the mixed attention+MLP+MoE+SSM stack
# served end to end (prefill + decode) from the bit-packed layout, packed
# vs dense logits allclose (bit-exact on the CPU ref backend).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python scripts/smoke_serve_packed.py

# Continuous-batching engine smoke: staggered admission + out-of-order
# completion over the packed mixed stack, every greedy stream equal to
# the one-shot loop's (the full matrix lives in tests/test_engine.py).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python scripts/smoke_engine.py

# Fault-tolerance smoke (hard gate): a seeded FaultPlan with every fault
# kind — injected decode failure, NaN-poisoned slot, page-pressure
# spike, kill-and-restore, preemption signal — driven through
# supervised_serve; every FINISHED stream must be bit-exact to the
# one-shot oracle and every other request typed.  CHAOS_report.json is
# uploaded next to the audit artifacts by CI.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python scripts/smoke_chaos.py \
    CHAOS_report.json

# Static serving-graph audit (hard gate): compile-time proof of the
# eq.-14 invariants over both committed golden fixtures — dense-inflation
# scan of every serve entry's jaxpr (pallas routes traced on CPU, no
# Mosaic), per-leaf HBM bytes/weight == bits_per_index(K)/8 from compiled
# HLO, the engine recompile gate, and VMEM/lane lint of every reachable
# block config.  Non-allowlisted violations exit 1 and fail the build;
# AUDIT_*.json is uploaded next to the bench artifact by CI.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.analysis.audit \
    --packed tests/fixtures/pr2_mlp_only --out AUDIT_pr2.json
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.analysis.audit \
    --packed tests/fixtures/pr3_full --out AUDIT_pr3.json

# Kernel + engine bench smoke (serve-path byte accounting, engine
# throughput rows, perf trajectory): the same CSV/JSON CI uploads as an
# artifact (BENCH_kernels.{csv,json}).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.run \
    --only kernels,engine --json BENCH_kernels.json | tee BENCH_kernels.csv
