"""Operations and bytes that the served work needs, from the model's
shapes and the tokens each step served.

Nothing here looks at what a kernel was handed: a decode step of 3 live
slots counts 3 tokens whatever batch the program pads it to, so the same
work is counted whatever implements it.  Packed weights count their
eq.-14 bytes, ``bits_per_index(K) / 8`` per weight, plus one f32
codebook per matrix; quantized KV counts ``kv_bits / 8`` bytes per
cached feature plus each page's codebooks.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import numpy as np

from harness.spec import ModelSpec

_ITEMSIZE = {"float32": 4, "bfloat16": 2}


def act_bytes(spec: ModelSpec) -> int:
    return _ITEMSIZE[spec.dtype]


def layer_matrices(spec: ModelSpec) -> List[Tuple[int, int]]:
    """(rows, cols) of the packed matrices of one layer."""
    d, f = spec.d_model, spec.d_ff
    qd, kvd = spec.n_heads * spec.head_dim, spec.n_kv * spec.head_dim
    return [(d, qd), (d, kvd), (d, kvd), (qd, d), (d, f), (d, f), (f, d)]


def matrix_bytes(spec: ModelSpec, rows: int, cols: int) -> float:
    """Eq.-14 bytes of one packed matrix and its f32 codebook."""
    return rows * cols * spec.bits / 8 + spec.k * 4


def packed_matmul(spec: ModelSpec, m: int, rows: int, cols: int
                  ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``m`` tokens through one packed matrix."""
    a = act_bytes(spec)
    return (2.0 * m * rows * cols,
            matrix_bytes(spec, rows, cols) + m * (rows + cols) * a)


def tied_head(spec: ModelSpec, m: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``m`` rows through the packed tied head, f32
    logits out."""
    v, d = spec.vocab, spec.d_model
    return (2.0 * m * v * d,
            matrix_bytes(spec, v, d) + m * d * act_bytes(spec) + m * v * 4)


def _calls(step) -> Iterable[Tuple[int, int]]:
    """(tokens through the layers, rows through the head) per device
    call of a step: one decode call, and one call per prefill block
    (the head sees the block's last position only)."""
    if step.decoded:
        yield step.decoded, step.decoded
    for _, width in step.blocks:
        yield width, 1


def packed_work(spec: ModelSpec, steps) -> Tuple[float, float]:
    """(FLOPs, bytes) of the packed matmuls and the tied head."""
    flops = nbytes = 0.0
    for step in steps:
        for m, m_head in _calls(step):
            for rows, cols in layer_matrices(spec):
                f, b = packed_matmul(spec, m, rows, cols)
                flops += f * spec.layers
                nbytes += b * spec.layers
            f, b = tied_head(spec, m_head)
            flops += f
            nbytes += b
    return flops, nbytes


def kv_bytes_per_position(spec: ModelSpec) -> float:
    """Bytes of K and V at one position of one layer."""
    feats = 2 * spec.n_kv * spec.head_dim
    if spec.kv_bits:
        return feats * spec.kv_bits / 8
    return feats * act_bytes(spec)


def kv_codebook_bytes_per_page(spec: ModelSpec) -> float:
    """One codebook per page for K and one for V, in the pool's dtype."""
    if not spec.kv_bits:
        return 0.0
    return 2 * (1 << spec.kv_bits) * act_bytes(spec)


def paged_attention_work(spec: ModelSpec, steps) -> Tuple[float, float]:
    """(FLOPs, bytes) of decode attention over each decoded slot's
    context: q·k and p·v for every head, the context's K/V read once."""
    flops = nbytes = 0.0
    hd, h = spec.head_dim, spec.n_heads
    for step in steps:
        for ctx in step.contexts:
            pages = -(-ctx // spec.page_size)
            flops += 4.0 * ctx * h * hd * spec.layers
            nbytes += spec.layers * (
                ctx * kv_bytes_per_position(spec)
                + pages * kv_codebook_bytes_per_page(spec)
                + 2 * h * hd * act_bytes(spec))
    return flops, nbytes


def model_flops(spec: ModelSpec, steps) -> float:
    """Model FLOPs of every token computed: 2 per matmul weight per token
    through the layers, the head where logits are taken, and attention
    over each token's causal context."""
    per_token = 2.0 * spec.layers * sum(r * c for r, c in
                                        layer_matrices(spec))
    head = 2.0 * spec.vocab * spec.d_model
    attn = 4.0 * spec.n_heads * spec.head_dim * spec.layers
    total = 0.0
    for step in steps:
        if step.decoded:
            total += step.decoded * (per_token + head)
            total += attn * sum(step.contexts)
        for start, width in step.blocks:
            total += width * per_token + head
            # query i of the block sees start + i + 1 positions
            total += attn * (width * start + width * (width + 1) / 2)
    return total


def least_time(flops: float, nbytes: float, peaks: Dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def share(least: float, measured_s: float):
    """A roofline share in percent; None where nothing was measured."""
    if measured_s <= 0 or not np.isfinite(measured_s):
        return None
    return 100.0 * least / measured_s
