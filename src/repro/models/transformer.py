"""Composable decoder stack covering all assigned architectures.

A model is a sequence of *stacks*; each stack is ``groups`` repetitions of
a layer ``pattern`` (tuple of LayerKind).  The forward scans over groups
with stacked parameters ([G, ...] leaves) so the HLO is compact regardless
of depth — 96-layer Nemotron compiles as fast as 2 layers.  Mixed layouts
(Gemma-2 local/global alternation, RecurrentGemma's rec-rec-attn 1:2
pattern, DeepSeek's dense-then-MoE split) are expressed as patterns /
multiple stacks, never as unrolled layers.

Three entry points per model:
  * ``loss_fn``      — training loss (next-token CE), full sequence;
  * ``prefill``      — forward + KV/state cache emission;
  * ``decode_step``  — one token with cache (the ``serve_step``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import qleaf as Q
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.sharding_ctx import constrain

Array = jax.Array


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int
    top_k: int
    n_shared: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class MLASpec:
    kv_lora: int = 512
    rope_dim: int = 64
    nope_dim: int = 128
    v_dim: int = 128


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    d_inner: int = 0
    head_p: int = 64
    state_n: int = 128
    conv_w: int = 4
    chunk: int = 256


@dataclasses.dataclass(frozen=True)
class RGLRUSpec:
    width: int = 0
    conv_w: int = 4


@dataclasses.dataclass(frozen=True)
class LayerKind:
    mixer: str          # gqa | gqa_local | mla | ssm | rglru
    mlp: str = "dense"  # dense | moe | none


@dataclasses.dataclass(frozen=True)
class StackSpec:
    pattern: Tuple[LayerKind, ...]
    groups: int


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    stacks: Tuple[StackSpec, ...]
    mlp_act: str = "silu"
    gated_mlp: bool = True
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    window: Optional[int] = None
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    query_scale: Optional[float] = None
    moe: Optional[MoESpec] = None
    mla: Optional[MLASpec] = None
    ssm: Optional[SSMSpec] = None
    rglru: Optional[RGLRUSpec] = None
    post_norms: bool = False
    emb_scale: Optional[float] = None
    pos_embed: str = "rope"        # rope | sinusoidal
    vlm_patches: int = 0
    q_chunk: int = 1024
    kv_chunk: int = 1024
    # codebook-quantized paged KV cache (serving): 0 = dense pages,
    # else bits ∈ {2,4,8}; kv_cb_mode ∈ {"page","head"} picks one
    # codebook per page or per (page, kv-head) — see core.kvquant.
    kv_bits: int = 0
    kv_cb_mode: str = "page"
    remat: bool = True
    remat_policy: str = "full"     # full (save nothing) | dots (save dot outs)
    attn_unroll: bool = False      # triangular causal schedule (nq ≤ 8)
    # notes for DESIGN/dry-run (e.g. long-context applicability)
    subquadratic: bool = False

    @property
    def n_layers(self) -> int:
        return sum(len(s.pattern) * s.groups for s in self.stacks)

    def param_count(self) -> int:
        """Analytic total param count (for 6·N·D roofline terms)."""
        import numpy as np
        shapes = jax.eval_shape(lambda k: init_params(k, self, jnp.float32),
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
        return int(sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(shapes)))

    def active_param_count(self) -> int:
        """Params touched per token (MoE: shared + top-k experts only)."""
        total = self.param_count()
        if self.moe is None:
            return total
        m = self.moe
        per_expert = 3 * self.d_model * m.d_ff_expert
        n_moe_layers = sum(
            sum(1 for k in s.pattern if k.mlp == "moe") * s.groups
            for s in self.stacks)
        inactive = n_moe_layers * (m.n_experts - m.top_k) * per_expert
        return total - inactive


def uniform_stack(kind: LayerKind, n_layers: int) -> Tuple[StackSpec, ...]:
    return (StackSpec(pattern=(kind,), groups=n_layers),)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(key: Array, cfg: ModelConfig, kind: LayerKind, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p: dict = {"ln1_norm_scale": jnp.zeros((cfg.d_model,), dtype)}

    if kind.mixer in ("gqa", "gqa_local"):
        p["mixer"] = attn.init_gqa(ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv,
                                   cfg.head_dim, cfg.qkv_bias, dtype)
    elif kind.mixer == "mla":
        m = cfg.mla
        p["mixer"] = attn.init_mla(ks[0], cfg.d_model, cfg.n_heads,
                                   kv_lora=m.kv_lora, rope_dim=m.rope_dim,
                                   nope_dim=m.nope_dim, v_dim=m.v_dim,
                                   dtype=dtype)
    elif kind.mixer == "ssm":
        s = cfg.ssm
        p["mixer"] = ssm_mod.init_ssm(ks[0], cfg.d_model, d_inner=s.d_inner,
                                      head_p=s.head_p, state_n=s.state_n,
                                      conv_w=s.conv_w, dtype=dtype)
    elif kind.mixer == "rglru":
        r = cfg.rglru
        p["mixer"] = rglru_mod.init_rglru_block(ks[0], cfg.d_model, r.width,
                                                r.conv_w, dtype)
    else:
        raise ValueError(kind.mixer)

    if kind.mlp != "none":
        p["ln2_norm_scale"] = jnp.zeros((cfg.d_model,), dtype)
        if kind.mlp == "moe":
            m = cfg.moe
            p["mlp"] = moe_mod.init_moe(ks[1], cfg.d_model, m.d_ff_expert,
                                        m.n_experts, m.n_shared, cfg.mlp_act,
                                        dtype)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_act,
                                  cfg.gated_mlp, dtype)
    if cfg.post_norms:
        p["post1_norm_scale"] = jnp.zeros((cfg.d_model,), dtype)
        if kind.mlp != "none":
            p["post2_norm_scale"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def init_params(key: Array, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    n_stacks = len(cfg.stacks)
    keys = jax.random.split(key, n_stacks + 2)
    params: dict = {
        "embed_tok": (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model))
                      * cfg.d_model ** -0.5).astype(dtype),
        "final_norm_scale": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["head_w"] = (jax.random.normal(keys[1], (cfg.d_model, cfg.vocab))
                            * cfg.d_model ** -0.5).astype(dtype)
    stacks = []
    for si, spec in enumerate(cfg.stacks):
        gkeys = jax.random.split(jax.random.fold_in(keys[2 + si], 7), spec.groups)
        stack = {}
        for pi, kind in enumerate(spec.pattern):
            pkeys = jax.vmap(lambda k: jax.random.fold_in(k, pi))(gkeys)
            stack[f"pos{pi}"] = jax.vmap(
                lambda k: _init_layer(k, cfg, kind, dtype))(pkeys)
        stacks.append(stack)
    params["stacks"] = tuple(stacks)
    return params


# ---------------------------------------------------------------------------
# Layer application (shared by train / prefill / decode)
# ---------------------------------------------------------------------------

def _apply_mixer_full(kind, p, x, positions, cfg):
    """Full-sequence mixer; returns (out, prefill_cache_entry)."""
    if kind.mixer in ("gqa", "gqa_local"):
        window = cfg.window if kind.mixer == "gqa_local" else None
        out, (k, v) = attn.gqa_forward(
            p, x, positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
            head_dim=cfg.head_dim, window=window,
            attn_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            query_scale=cfg.query_scale, causal_unroll=cfg.attn_unroll)
        return out, {"k": k, "v": v}
    if kind.mixer == "mla":
        m = cfg.mla
        out, cache = attn.mla_forward(
            p, x, positions, n_heads=cfg.n_heads, kv_lora=m.kv_lora,
            rope_dim=m.rope_dim, nope_dim=m.nope_dim, v_dim=m.v_dim,
            rope_theta=cfg.rope_theta, q_chunk=cfg.q_chunk,
            kv_chunk=cfg.kv_chunk)
        return out, cache
    if kind.mixer == "ssm":
        s = cfg.ssm
        out, state = ssm_mod.ssm_forward(p, x, d_inner=s.d_inner,
                                         head_p=s.head_p, state_n=s.state_n,
                                         chunk=s.chunk)
        return out, {"state": state}
    if kind.mixer == "rglru":
        out, state = rglru_mod.rglru_forward(p, x, width=cfg.rglru.width)
        return out, {"state": state}
    raise ValueError(kind.mixer)


def _apply_layer_full(kind, p, x, positions, cfg):
    h = L.rms_norm(x, p["ln1_norm_scale"])
    out, _ = _apply_mixer_full(kind, p["mixer"], h, positions, cfg)
    if cfg.post_norms:
        out = L.rms_norm(out, p["post1_norm_scale"])
    x = constrain(x + out, "batch", None, None)
    if kind.mlp != "none":
        h = L.rms_norm(x, p["ln2_norm_scale"])
        if kind.mlp == "moe":
            out = moe_mod.apply_moe(p["mlp"], h, top_k=cfg.moe.top_k,
                                    act=cfg.mlp_act,
                                    capacity_factor=cfg.moe.capacity_factor)
        else:
            out = L.apply_mlp(p["mlp"], h, cfg.mlp_act)
        if cfg.post_norms:
            out = L.rms_norm(out, p["post2_norm_scale"])
        x = constrain(x + out, "batch", None, None)
    return x


def _apply_stack_full(spec: StackSpec, stack_params, x, positions, cfg):
    def body(carry, group_params):
        h = carry
        for pi, kind in enumerate(spec.pattern):
            h = _apply_layer_full(kind, group_params[f"pos{pi}"], h,
                                  positions, cfg)
        return h, None

    if cfg.remat:
        policy = (jax.checkpoint_policies.checkpoint_dots
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)
    x, _ = jax.lax.scan(body, x, stack_params)
    return x


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def _embed(params, cfg, tokens, patch_embeds=None, positions=None):
    # Dense gather, or dequant-on-gather when the table serves quantized
    # (packed indices → shift+mask → LUT; dispatch.quantized_gather).
    # ``positions``: global position ids [S] for a mid-prompt block
    # (blockwise prefill); defaults to arange(S).
    x = Q.qembed(params, "embed_tok", tokens)
    if cfg.emb_scale is not None:
        x = x * jnp.asarray(cfg.emb_scale, x.dtype)
    if cfg.pos_embed == "sinusoidal":
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        x = x + L.sinusoidal_positions(
            positions, cfg.d_model)[None].astype(x.dtype)
    if cfg.vlm_patches and patch_embeds is not None:
        x = jax.lax.dynamic_update_slice(
            x, patch_embeds.astype(x.dtype), (0, 0, 0))
    return constrain(x, "batch", None, None)


def _head(params, cfg, x):
    x = L.rms_norm(x, params["final_norm_scale"])
    if cfg.tie_embeddings:
        logits = Q.qmatmul_t(params, "embed_tok", x)
    else:
        logits = Q.qmatmul(params, "head_w", x)
    logits = constrain(logits, "batch", None, "vocab")
    return L.softcap(logits.astype(jnp.float32), cfg.final_softcap)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, tokens: Array,
            patch_embeds: Optional[Array] = None) -> Array:
    """[B, S] tokens → [B, S, V] logits (f32)."""
    s = tokens.shape[1]
    positions = jnp.arange(s)
    x = _embed(params, cfg, tokens, patch_embeds)
    for spec, sp in zip(cfg.stacks, params["stacks"]):
        x = _apply_stack_full(spec, sp, x, positions, cfg)
    return _head(params, cfg, x)


def loss_fn(params, cfg: ModelConfig, batch: dict) -> Array:
    """Mean next-token cross-entropy.  batch: tokens, labels[, patch_embeds]."""
    logits = forward(params, cfg, batch["tokens"],
                     batch.get("patch_embeds"))
    labels = batch["labels"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# --- caches -----------------------------------------------------------------

def _init_layer_cache(kind: LayerKind, cfg: ModelConfig, batch: int,
                      capacity: int, dtype):
    if kind.mixer == "gqa":
        return attn.init_kv_cache(batch, capacity, cfg.n_kv, cfg.head_dim,
                                  dtype=dtype)
    if kind.mixer == "gqa_local":
        cap = min(capacity, cfg.window or capacity)
        return attn.init_kv_cache(batch, cap, cfg.n_kv, cfg.head_dim,
                                  dtype=dtype)
    if kind.mixer == "mla":
        m = cfg.mla
        return attn.init_mla_cache(batch, capacity, m.kv_lora, m.rope_dim, dtype)
    if kind.mixer == "ssm":
        s = cfg.ssm
        return ssm_mod.init_ssm_cache(batch, s.d_inner, s.head_p, s.state_n,
                                      s.conv_w, dtype)
    if kind.mixer == "rglru":
        return rglru_mod.init_rglru_cache(batch, cfg.rglru.width,
                                          cfg.rglru.conv_w, dtype)
    raise ValueError(kind.mixer)


def init_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=jnp.float32):
    """Stacked caches mirroring the param stacks: leaves [G, ...]."""
    caches = []
    for spec in cfg.stacks:
        stack = {}
        for pi, kind in enumerate(spec.pattern):
            one = _init_layer_cache(kind, cfg, batch, capacity, dtype)
            stack[f"pos{pi}"] = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (spec.groups,) + x.shape),
                one)
        caches.append(stack)
    return tuple(caches)


def _apply_mixer_decode(kind, p, x_t, cache, pos, cfg):
    if kind.mixer in ("gqa", "gqa_local"):
        local = kind.mixer == "gqa_local"
        return attn.gqa_decode(p, x_t, cache, pos, n_heads=cfg.n_heads,
                               n_kv=cfg.n_kv, head_dim=cfg.head_dim,
                               ring=local, window=cfg.window if local else None,
                               attn_softcap=cfg.attn_softcap,
                               rope_theta=cfg.rope_theta,
                               query_scale=cfg.query_scale)
    if kind.mixer == "mla":
        m = cfg.mla
        return attn.mla_decode(p, x_t, cache, pos, n_heads=cfg.n_heads,
                               kv_lora=m.kv_lora, rope_dim=m.rope_dim,
                               nope_dim=m.nope_dim, v_dim=m.v_dim,
                               rope_theta=cfg.rope_theta)
    if kind.mixer == "ssm":
        s = cfg.ssm
        return ssm_mod.ssm_decode(p, x_t, cache, d_inner=s.d_inner,
                                  head_p=s.head_p, state_n=s.state_n)
    if kind.mixer == "rglru":
        return rglru_mod.rglru_decode(p, x_t, cache, width=cfg.rglru.width)
    raise ValueError(kind.mixer)


def _apply_layer_decode(kind, p, x_t, cache, pos, cfg):
    h = L.rms_norm(x_t, p["ln1_norm_scale"])
    out, cache = _apply_mixer_decode(kind, p["mixer"], h, cache, pos, cfg)
    if cfg.post_norms:
        out = L.rms_norm(out, p["post1_norm_scale"])
    x_t = x_t + out
    if kind.mlp != "none":
        h = L.rms_norm(x_t, p["ln2_norm_scale"])
        if kind.mlp == "moe":
            out = moe_mod.apply_moe(p["mlp"], h, top_k=cfg.moe.top_k,
                                    act=cfg.mlp_act,
                                    capacity_factor=cfg.moe.capacity_factor)
        else:
            out = L.apply_mlp(p["mlp"], h, cfg.mlp_act)
        if cfg.post_norms:
            out = L.rms_norm(out, p["post2_norm_scale"])
        x_t = x_t + out
    return x_t, cache


def decode_step(params, cfg: ModelConfig, caches, tokens_t: Array, pos):
    """serve_step: one new token per sequence with existing caches.

    tokens_t: [B, 1] int32; pos: scalar int32 (current position).
    Returns (logits [B, 1, V], new caches).
    """
    x = Q.qembed(params, "embed_tok", tokens_t)
    if cfg.emb_scale is not None:
        x = x * jnp.asarray(cfg.emb_scale, x.dtype)
    if cfg.pos_embed == "sinusoidal":
        x = x + L.sinusoidal_positions(
            jnp.asarray(pos)[None], cfg.d_model)[None].astype(x.dtype)

    new_caches = []
    for spec, sp, sc in zip(cfg.stacks, params["stacks"], caches):
        def body(carry, xs):
            h = carry
            gp, gc = xs
            new_gc = {}
            for pi, kind in enumerate(spec.pattern):
                h, c = _apply_layer_decode(kind, gp[f"pos{pi}"], h,
                                           gc[f"pos{pi}"], pos, cfg)
                new_gc[f"pos{pi}"] = c
            return h, new_gc

        x, nc = jax.lax.scan(body, x, (sp, sc))
        new_caches.append(nc)
    return _head(params, cfg, x), tuple(new_caches)


# --- paged caches (continuous-batching engine) ------------------------------
#
# Global-attention layers share one physical page pool per layer position
# ([G, n_pages + 1, page, ...]; page 0 is the reserved trash page) indexed
# by ONE per-slot page table — every layer caches the same logical
# positions, so the table is model-wide, not per-layer.  SSM / RG-LRU /
# sliding-window layers keep constant-size per-slot state ([G, n_slots,
# ...]) that simply resets on admission.  ``decode_step_slots`` is the
# engine's serve step: fixed shapes for any admission/eviction state, so
# admitting a request never recompiles.


def _init_layer_paged_cache(kind: LayerKind, cfg: ModelConfig, n_slots: int,
                            n_pages: int, page_size: int, dtype):
    if kind.mixer == "gqa":
        if cfg.kv_bits:
            return attn.init_quant_paged_kv_cache(
                n_pages, page_size, cfg.n_kv, cfg.head_dim, cfg.kv_bits,
                cfg.kv_cb_mode, dtype)
        return attn.init_paged_kv_cache(n_pages, page_size, cfg.n_kv,
                                        cfg.head_dim, dtype)
    if kind.mixer == "gqa_local":
        # ring buffers stay dense: constant-size per-slot state, no pages
        return attn.init_kv_cache(n_slots, cfg.window or n_pages * page_size,
                                  cfg.n_kv, cfg.head_dim, dtype)
    if kind.mixer == "mla":
        m = cfg.mla
        if cfg.kv_bits:
            return attn.init_quant_paged_mla_cache(
                n_pages, page_size, m.kv_lora, m.rope_dim, cfg.kv_bits,
                dtype)
        return attn.init_paged_mla_cache(n_pages, page_size, m.kv_lora,
                                         m.rope_dim, dtype)
    if kind.mixer == "ssm":
        s = cfg.ssm
        return ssm_mod.init_ssm_cache(n_slots, s.d_inner, s.head_p,
                                      s.state_n, s.conv_w, dtype)
    if kind.mixer == "rglru":
        return rglru_mod.init_rglru_cache(n_slots, cfg.rglru.width,
                                          cfg.rglru.conv_w, dtype)
    raise ValueError(kind.mixer)


def init_paged_cache(cfg: ModelConfig, n_slots: int, n_pages: int,
                     page_size: int, dtype=jnp.float32):
    """Engine decode caches mirroring the param stacks: leaves [G, ...]."""
    caches = []
    for spec in cfg.stacks:
        stack = {}
        for pi, kind in enumerate(spec.pattern):
            one = _init_layer_paged_cache(kind, cfg, n_slots, n_pages,
                                          page_size, dtype)
            stack[f"pos{pi}"] = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (spec.groups,) + x.shape),
                one)
        caches.append(stack)
    return tuple(caches)


def _gate_slot_cache(new, old, alive: Array):
    """Keep masked slots' per-slot state untouched (page-starved slots
    must resume bit-exactly; leading cache dim is the slot dim)."""
    def sel(n, o):
        m = alive.reshape((alive.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree_util.tree_map(sel, new, old)


def _apply_mixer_decode_slots(kind, p, x_t, cache, layer, page_table, pos,
                              alive, cfg):
    if kind.mixer == "gqa":
        if isinstance(cache, attn.QuantPagedKVCache):
            page_size = cache.k_words.shape[1]
            return attn.gqa_decode_paged_quant(
                p, x_t, cache, page_table, pos, alive, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv, head_dim=cfg.head_dim, page_size=page_size,
                kv_bits=cfg.kv_bits, kv_cb_mode=cfg.kv_cb_mode,
                attn_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
                query_scale=cfg.query_scale)
        page_size = cache.k.shape[2]            # the stack's pools, whole
        return attn.gqa_decode_paged(
            p, x_t, cache, layer, page_table, pos, alive,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
            page_size=page_size,
            attn_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
            query_scale=cfg.query_scale)
    if kind.mixer == "gqa_local":
        out, c = attn.gqa_decode_ring_slots(
            p, x_t, cache, pos, alive, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
            head_dim=cfg.head_dim, window=cfg.window,
            attn_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
            query_scale=cfg.query_scale)
        return out, _gate_slot_cache(c, cache, alive)
    if kind.mixer == "mla":
        m = cfg.mla
        if isinstance(cache, attn.QuantPagedMLACache):
            page_size = cache.c_words.shape[1]
            return attn.mla_decode_paged_quant(
                p, x_t, cache, page_table, pos, alive, n_heads=cfg.n_heads,
                kv_lora=m.kv_lora, rope_dim=m.rope_dim, nope_dim=m.nope_dim,
                v_dim=m.v_dim, page_size=page_size, kv_bits=cfg.kv_bits,
                rope_theta=cfg.rope_theta)
        page_size = cache.c_kv.shape[1]
        return attn.mla_decode_paged(
            p, x_t, cache, page_table, pos, alive, n_heads=cfg.n_heads,
            kv_lora=m.kv_lora, rope_dim=m.rope_dim, nope_dim=m.nope_dim,
            v_dim=m.v_dim, page_size=page_size, rope_theta=cfg.rope_theta)
    if kind.mixer == "ssm":
        s = cfg.ssm
        out, c = ssm_mod.ssm_decode(p, x_t, cache, d_inner=s.d_inner,
                                    head_p=s.head_p, state_n=s.state_n)
        return out, _gate_slot_cache(c, cache, alive)
    if kind.mixer == "rglru":
        out, c = rglru_mod.rglru_decode(p, x_t, cache, width=cfg.rglru.width)
        return out, _gate_slot_cache(c, cache, alive)
    raise ValueError(kind.mixer)


def _apply_layer_decode_slots(kind, p, x_t, cache, layer, page_table, pos,
                              alive, cfg):
    # named scopes: the device ops of each region carry them in the
    # profiler's trace (the mixer's scope is "attn" for every kind)
    with jax.named_scope("attn"):
        h = L.rms_norm(x_t, p["ln1_norm_scale"])
        out, cache = _apply_mixer_decode_slots(kind, p["mixer"], h, cache,
                                               layer, page_table, pos,
                                               alive, cfg)
        if cfg.post_norms:
            out = L.rms_norm(out, p["post1_norm_scale"])
        x_t = x_t + out
    if kind.mlp != "none":
        with jax.named_scope("mlp"):
            h = L.rms_norm(x_t, p["ln2_norm_scale"])
            if kind.mlp == "moe":
                out = moe_mod.apply_moe(
                    p["mlp"], h, top_k=cfg.moe.top_k, act=cfg.mlp_act,
                    capacity_factor=cfg.moe.capacity_factor)
            else:
                out = L.apply_mlp(p["mlp"], h, cfg.mlp_act)
            if cfg.post_norms:
                out = L.rms_norm(out, p["post2_norm_scale"])
            x_t = x_t + out
    return x_t, cache


def decode_step_slots(params, cfg: ModelConfig, caches, page_table,
                      tokens_t: Array, pos: Array, alive: Array):
    """Slot-aware serve step for the continuous-batching engine.

    tokens_t [B, 1] int32 (B = n_slots); pos [B] int32 per-slot write
    positions; alive [B] bool.  Dead / page-starved slots are masked:
    their attention reads are invalid, their pool writes land on the
    reserved trash page, and their per-slot state (ring / SSM / RG-LRU)
    is left untouched.  Returns (logits [B, 1, V], new caches); shapes
    are independent of which slots are live, so admission never
    recompiles.
    """
    with jax.named_scope("embed"):
        x = Q.qembed(params, "embed_tok", tokens_t)
        if cfg.emb_scale is not None:
            x = x * jnp.asarray(cfg.emb_scale, x.dtype)
        if cfg.pos_embed == "sinusoidal":
            x = x + L.sinusoidal_positions(pos[:, None],
                                           cfg.d_model).astype(x.dtype)

    new_caches = []
    for spec, sp, sc in zip(cfg.stacks, params["stacks"], caches):
        # dense page pools ride the carry whole and are written in place
        # at [layer, page, offset]; every other cache is sliced per layer
        pools = {n: c for n, c in sc.items()
                 if isinstance(c, attn.PagedKVCache)}
        rest = {n: c for n, c in sc.items() if n not in pools}

        def body(carry, xs):
            h, pools = carry
            gp, gc, layer = xs
            pools, new_gc = dict(pools), {}
            for pi, kind in enumerate(spec.pattern):
                n = f"pos{pi}"
                held = pools if n in pools else gc
                h, c = _apply_layer_decode_slots(
                    kind, gp[n], h, held[n], layer, page_table, pos, alive,
                    cfg)
                (pools if n in pools else new_gc)[n] = c
            return (h, pools), new_gc

        (x, pools), nc = jax.lax.scan(
            body, (x, pools), (sp, rest, jnp.arange(spec.groups)))
        new_caches.append({**nc, **pools})
    with jax.named_scope("head"):
        logits = _head(params, cfg, x)
    return logits, tuple(new_caches)


# Default prompt-block length for the one-shot (oracle) blockwise
# prefill.  The engine's block length is its `prefill_chunk`; engine
# differential tests must run the oracle with the engine's effective
# chunk so both sides see the same block partition (the flash recurrence
# is partition-sensitive at the bit level).
DEFAULT_PREFILL_BLOCK = 64


def _init_layer_block_state(kind: LayerKind, cfg: ModelConfig, batch: int,
                            dtype):
    """Initial blockwise-prefill carry for one layer (unstacked).

    gqa/mla carry *growing* K/V (latent) buffers starting at length 0;
    gqa_local carries a ring of capacity ``cfg.window`` (the engine's
    per-slot ring capacity — required so engine and oracle views tile
    identically); ssm/rglru carry their decode caches (state + raw conv
    tails)."""
    if kind.mixer == "gqa":
        e = jnp.zeros((batch, 0, cfg.n_kv, cfg.head_dim), dtype)
        return attn.KVCache(k=e, v=e)
    if kind.mixer == "gqa_local":
        if not cfg.window:
            raise ValueError("blockwise prefill needs a finite cfg.window "
                             "for gqa_local layers (ring capacity)")
        z = jnp.zeros((batch, cfg.window, cfg.n_kv, cfg.head_dim), dtype)
        return attn.KVCache(k=z, v=z)
    if kind.mixer == "mla":
        m = cfg.mla
        return attn.MLACache(
            c_kv=jnp.zeros((batch, 0, m.kv_lora), dtype),
            k_rope=jnp.zeros((batch, 0, m.rope_dim), dtype))
    if kind.mixer == "ssm":
        s = cfg.ssm
        return ssm_mod.init_ssm_cache(batch, s.d_inner, s.head_p,
                                      s.state_n, s.conv_w, dtype)
    if kind.mixer == "rglru":
        return rglru_mod.init_rglru_cache(batch, cfg.rglru.width,
                                          cfg.rglru.conv_w, dtype)
    raise ValueError(kind.mixer)


def _apply_mixer_block(kind, p, x, state, start, cfg):
    """One prompt block through a mixer, carrying its prefill state."""
    if kind.mixer == "gqa":
        out, bk, bv = attn.gqa_prefill_block(
            p, x, state.k, state.v, start, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv, head_dim=cfg.head_dim,
            attn_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
            query_scale=cfg.query_scale)
        return out, attn.KVCache(k=bk, v=bv)
    if kind.mixer == "gqa_local":
        return attn.gqa_prefill_block_ring(
            p, x, state, start, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
            head_dim=cfg.head_dim, window=cfg.window,
            attn_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
            query_scale=cfg.query_scale)
    if kind.mixer == "mla":
        m = cfg.mla
        out, bc, br = attn.mla_prefill_block(
            p, x, state.c_kv, state.k_rope, start, n_heads=cfg.n_heads,
            kv_lora=m.kv_lora, rope_dim=m.rope_dim, nope_dim=m.nope_dim,
            v_dim=m.v_dim, rope_theta=cfg.rope_theta)
        return out, attn.MLACache(c_kv=bc, k_rope=br)
    if kind.mixer == "ssm":
        s = cfg.ssm
        return ssm_mod.ssm_block_forward(p, x, state, d_inner=s.d_inner,
                                         head_p=s.head_p,
                                         state_n=s.state_n, chunk=s.chunk)
    if kind.mixer == "rglru":
        return rglru_mod.rglru_block_forward(p, x, state,
                                             width=cfg.rglru.width)
    raise ValueError(kind.mixer)


def _apply_layer_block(kind, p, x, state, start, cfg):
    h = L.rms_norm(x, p["ln1_norm_scale"])
    out, state = _apply_mixer_block(kind, p["mixer"], h, state, start, cfg)
    if cfg.post_norms:
        out = L.rms_norm(out, p["post1_norm_scale"])
    x = x + out
    if kind.mlp != "none":
        h = L.rms_norm(x, p["ln2_norm_scale"])
        if kind.mlp == "moe":
            out = moe_mod.apply_moe(p["mlp"], h, top_k=cfg.moe.top_k,
                                    act=cfg.mlp_act,
                                    capacity_factor=cfg.moe.capacity_factor)
        else:
            out = L.apply_mlp(p["mlp"], h, cfg.mlp_act)
        if cfg.post_norms:
            out = L.rms_norm(out, p["post2_norm_scale"])
        x = x + out
    return x, state


def _block_state_to_cache(kind: LayerKind, state, s: int,
                          cfg: ModelConfig):
    """Final blockwise-prefill carry → decode-cache layout (leaves keep
    their leading [G] group dim).  Same contract the full-sequence
    prefill used to emit — except ssm/rglru conv tails are now the
    *real* trailing raw activations, not zeros, so decode resumes the
    conv streams exactly."""
    if kind.mixer == "gqa_local":
        w = cfg.window
        if s < w:
            # ring never wrapped: natural order, capacity = S (grown by
            # the decode loop); at S ≥ W the ring layout is already
            # positions mod W
            return attn.KVCache(k=state.k[:, :, :s], v=state.v[:, :, :s])
        return state
    return state


def prefill(params, cfg: ModelConfig, tokens: Array,
            patch_embeds: Optional[Array] = None,
            last_logits_only: bool = False,
            block: Optional[int] = None):
    """Blockwise forward over the prompt, emitting logits + decode caches.

    The prompt runs in fixed blocks of ``block`` tokens (default
    :data:`DEFAULT_PREFILL_BLOCK`, remainder last); every block attends
    over the carried K/V written so far via the online-softmax blockwise
    op (``dispatch.blockwise_prefill_attention``), and SSM / RG-LRU /
    ring layers carry their recurrent state across blocks.  Peak
    activation memory is O(block·S) in attention reads but O(block) in
    scores/logits — never O(S²).

    ``last_logits_only=True`` (the serving configuration) heads only the
    final position — full-sequence f32 logits over a 150k-250k vocab are
    a multi-GB/chip buffer that serving never needs.

    ``patch_embeds`` (VLM) forces a single block: patch rows replace the
    leading positions at embed time.

    Emits *full-length* caches for gqa/mla layers (capacity = S);
    ring-buffer layers keep the last ``window`` entries.
    """
    b, s = tokens.shape
    if patch_embeds is not None:
        blk = s
    else:
        blk = max(1, min(block or DEFAULT_PREFILL_BLOCK, s))
    starts = list(range(0, s, blk))
    states = None
    logits_parts = []
    for start in starts:
        end = min(start + blk, s)
        tok_blk = jax.lax.slice_in_dim(tokens, start, end, axis=1)
        x = _embed(params, cfg, tok_blk, patch_embeds,
                   positions=jnp.arange(start, end))
        if states is None:
            states = [
                {f"pos{pi}": jax.tree_util.tree_map(
                    lambda l: jnp.broadcast_to(
                        l[None], (spec.groups,) + l.shape),
                    _init_layer_block_state(kind, cfg, b, x.dtype))
                 for pi, kind in enumerate(spec.pattern)}
                for spec in cfg.stacks]
        new_states = []
        for spec, sp, st in zip(cfg.stacks, params["stacks"], states):
            def body(h, xs):
                gp, gst = xs
                ngst = {}
                for pi, kind in enumerate(spec.pattern):
                    h, c = _apply_layer_block(kind, gp[f"pos{pi}"], h,
                                              gst[f"pos{pi}"], start, cfg)
                    ngst[f"pos{pi}"] = c
                return h, ngst

            x, nst = jax.lax.scan(body, x, (sp, st))
            new_states.append(nst)
        states = new_states
        if not last_logits_only:
            logits_parts.append(_head(params, cfg, x))
        elif start == starts[-1]:
            logits_parts.append(_head(params, cfg, x[:, -1:, :]))
    logits = (logits_parts[0] if len(logits_parts) == 1
              else jnp.concatenate(logits_parts, axis=1))
    caches = tuple(
        {f"pos{pi}": _block_state_to_cache(kind, st[f"pos{pi}"], s, cfg)
         for pi, kind in enumerate(spec.pattern)}
        for spec, st in zip(cfg.stacks, states))
    return logits, caches


# --- engine-side blockwise prefill (one slot, one block) --------------------


def _apply_mixer_prefill_slot(kind, p, x, cache, table_row, sl, start,
                              alive, cfg):
    """One prompt block of one *slot* against the engine's paged /
    per-slot caches.  ``cache`` leaves are unstacked (the group scan
    strips [G]); ``table_row`` [1, npg]; ``sl`` [1] traced slot id."""
    if kind.mixer == "gqa":
        if isinstance(cache, attn.QuantPagedKVCache):
            page_size = cache.k_words.shape[1]
            return attn.gqa_prefill_block_paged_quant(
                p, x, cache, table_row, start, alive, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv, head_dim=cfg.head_dim, page_size=page_size,
                kv_bits=cfg.kv_bits, kv_cb_mode=cfg.kv_cb_mode,
                attn_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
                query_scale=cfg.query_scale)
        page_size = cache.k.shape[1]            # one layer: the scan's slice
        return attn.gqa_prefill_block_paged(
            p, x, cache, table_row, start, alive, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv, head_dim=cfg.head_dim, page_size=page_size,
            attn_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
            query_scale=cfg.query_scale)
    if kind.mixer == "mla":
        m = cfg.mla
        if isinstance(cache, attn.QuantPagedMLACache):
            page_size = cache.c_words.shape[1]
            return attn.mla_prefill_block_paged_quant(
                p, x, cache, table_row, start, alive, n_heads=cfg.n_heads,
                kv_lora=m.kv_lora, rope_dim=m.rope_dim,
                nope_dim=m.nope_dim, v_dim=m.v_dim, page_size=page_size,
                kv_bits=cfg.kv_bits, rope_theta=cfg.rope_theta)
        page_size = cache.c_kv.shape[1]
        return attn.mla_prefill_block_paged(
            p, x, cache, table_row, start, alive, n_heads=cfg.n_heads,
            kv_lora=m.kv_lora, rope_dim=m.rope_dim, nope_dim=m.nope_dim,
            v_dim=m.v_dim, page_size=page_size, rope_theta=cfg.rope_theta)
    # per-slot state rows (ring / ssm / rglru): pull the slot's row,
    # run the same block function the oracle runs, scatter it back
    row = jax.tree_util.tree_map(lambda l: jnp.take(l, sl, axis=0), cache)
    if kind.mixer in ("ssm", "rglru"):
        # block 0 of a *reused* slot must not consume the previous
        # request's recurrent state: the fresh row is all-zero.  (The
        # ring needs no reset — _ring_positions derives validity from
        # ``start``, so stale rows mask out on their own.)
        row = jax.tree_util.tree_map(
            lambda l: jnp.where(start == 0, jnp.zeros_like(l), l), row)
    if kind.mixer == "gqa_local":
        out, c = attn.gqa_prefill_block_ring(
            p, x, row, start, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
            head_dim=cfg.head_dim, window=cfg.window,
            attn_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
            query_scale=cfg.query_scale)
    elif kind.mixer == "ssm":
        s = cfg.ssm
        out, c = ssm_mod.ssm_block_forward(p, x, row, d_inner=s.d_inner,
                                           head_p=s.head_p,
                                           state_n=s.state_n, chunk=s.chunk)
    elif kind.mixer == "rglru":
        out, c = rglru_mod.rglru_block_forward(p, x, row,
                                               width=cfg.rglru.width)
    else:
        raise ValueError(kind.mixer)
    new = jax.tree_util.tree_map(
        lambda dst, src: dst.at[sl[0]].set(src[0].astype(dst.dtype)),
        cache, c)
    return out, new


def _apply_layer_prefill_slot(kind, p, x, cache, table_row, sl, start,
                              alive, cfg):
    h = L.rms_norm(x, p["ln1_norm_scale"])
    out, cache = _apply_mixer_prefill_slot(kind, p["mixer"], h, cache,
                                           table_row, sl, start, alive, cfg)
    if cfg.post_norms:
        out = L.rms_norm(out, p["post1_norm_scale"])
    x = x + out
    if kind.mlp != "none":
        h = L.rms_norm(x, p["ln2_norm_scale"])
        if kind.mlp == "moe":
            out = moe_mod.apply_moe(p["mlp"], h, top_k=cfg.moe.top_k,
                                    act=cfg.mlp_act,
                                    capacity_factor=cfg.moe.capacity_factor)
        else:
            out = L.apply_mlp(p["mlp"], h, cfg.mlp_act)
        if cfg.post_norms:
            out = L.rms_norm(out, p["post2_norm_scale"])
        x = x + out
    return x, cache


def prefill_chunk_slots(params, cfg: ModelConfig, caches, page_table,
                        tokens_c: Array, slot, start):
    """Engine blockwise prefill: ONE block of ``c`` prompt tokens for ONE
    slot, against the shared paged caches.

    tokens_c [1, c] int32 (positions [start, start+c)); ``slot`` and
    ``start`` are traced int32 scalars — compiled shapes depend only on
    ``c``, so chunk steps never recompile per slot or offset.  The
    block's K/V (quantized when ``kv_bits > 0``) lands directly in the
    slot's pages; recurrent state (ring / SSM / RG-LRU rows) advances in
    place.  Returns (last-position logits [1, 1, V] f32, new caches) —
    the logits are only meaningful on the prompt's final block, where
    they seed the first sampled token.  Its device ops carry the named
    scope ``prefill``.
    """
    with jax.named_scope("prefill"):
        c = tokens_c.shape[1]
        sl = jnp.asarray(slot, jnp.int32).reshape(1)
        start = jnp.asarray(start, jnp.int32)
        alive = jnp.ones((1,), bool)
        table_row = jnp.take(page_table, sl, axis=0)
        x = _embed(params, cfg, tokens_c, positions=start + jnp.arange(c))
        new_caches = []
        for spec, sp, sc in zip(cfg.stacks, params["stacks"], caches):
            def body(h, xs):
                gp, gc = xs
                ngc = {}
                for pi, kind in enumerate(spec.pattern):
                    h, cc = _apply_layer_prefill_slot(
                        kind, gp[f"pos{pi}"], h, gc[f"pos{pi}"], table_row,
                        sl, start, alive, cfg)
                    ngc[f"pos{pi}"] = cc
                return h, ngc

            x, nc = jax.lax.scan(body, x, (sp, sc))
            new_caches.append(nc)
        return _head(params, cfg, x[:, -1:, :]), tuple(new_caches)
