"""Pallas kernel static checks: VMEM footprint + grid/divisibility lint.

A bad (bm, bn, bk) entry in ``dispatch._PACKED_BLOCK_TABLE`` (or a bad
``REPRO_PACKED_BLOCKS`` override) fails at Mosaic *compile* time on a
TPU — which CI doesn't have.  This module re-derives, from the same
BlockSpecs the kernels declare, what Mosaic would be asked to fit:

* per-grid-step VMEM bytes — DMA'd blocks ×2 for the pipeline's double
  buffering, plus the unpack/dequant intermediates the kernel body
  creates — checked against a conservative budget (TPU VMEM is ~16 MB
  per core; see the Pallas guide's memory-space table);
* lane-divisibility: the word-packed axis's block must be a multiple of
  ``lanes = 32 // bits`` so uint32 words never straddle a block boundary
  (``bk`` for the forward kernel and the row-order transposed kernel,
  ``bn`` for the kd-order transposed kernel — exactly the ValueErrors
  the kernels raise, surfaced without tracing);
* tiling hygiene: ``bm % 8`` (f32 sublane), last-dim ``% 128`` (lane)
  misalignment — warnings, not errors, since Mosaic pads.

Everything here is integer arithmetic over static shapes: it runs on
CPU, no Mosaic, no TPU.  :func:`audit_block_space` sweeps every block
config reachable from the autotune surface — each ``_PACKED_BLOCK_TABLE``
entry plus ``packed_block_sizes``/``packed_block_sizes_t`` evaluated at
representative serve M values for every packed leaf of an artifact.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

from repro.core import kvquant
from repro.core.compression import PackedLayout, bits_per_index
from repro.kernels import dispatch

VMEM_BYTES = 16 * 1024 * 1024          # per TPU core (Pallas guide)
# Leave headroom for Mosaic's own staging + the K-entry LUT replication.
VMEM_BUDGET = int(0.75 * VMEM_BYTES)

# Decode micro-batch / prefill M values the serve paths actually emit.
SERVE_M = (1, 8, 64, 256)

KINDS = ("packed_matmul", "packed_matmul_t", "gather")

PAGED_KINDS = ("gqa", "mla", "gather")

# Upper bound on the query-side floats resident per grid step of a paged
# decode kernel: q block + out block + the m/l/acc online-softmax
# scratch.  Covers ≤128 query heads × ≤512 per-head features (hd for
# gqa, kv_lora for absorbed MLA) — far beyond the committed configs, and
# still <2 MiB against the budget.
PAGED_Q_SIDE_FLOATS = 128 * 512


def estimate_vmem_bytes(kind: str, bm: int, bn: int, bk: int, bits: int,
                        k: int, *, order: str = "kd") -> int:
    """Per-grid-step VMEM bytes a kernel asks Mosaic to resident-fit.

    Mirrors the BlockSpecs in ``kernels/codebook_matmul_packed{,_t}.py``
    and ``kernels/quantized_gather.py``: DMA'd input/output blocks count
    ×2 (pipeline double buffering); the in-kernel unpack index tile and
    dequantized weight tile count once.
    """
    lanes = 32 // bits
    f32, u32, i32 = 4, 4, 4
    if kind == "packed_matmul":
        # x[bm,bk] · unpack(pidx[bk//lanes, bn]) with cb[1,K] → out[bm,bn]
        dma = bm * bk * f32 + (bk // lanes) * bn * u32 + k * f32 \
            + bm * bn * f32
        tile = (bk, bn)
    elif kind == "packed_matmul_t":
        # x[bm,bk] · unpack(pidx).T; word block is [bn//lanes, bk] (kd:
        # V packed) or [bn, bk//lanes] (row: D packed) — same byte count.
        dma = bm * bk * f32 + (bn * bk // lanes) * u32 + k * f32 \
            + bm * bn * f32
        tile = (bn, bk)
    elif kind == "gather":
        # One packed word row [1, bk//lanes] → out row [1, bk]; bm/bn
        # unused (the grid is one step per token).
        dma = (bk // lanes) * u32 + k * f32 + bk * f32
        tile = (1, bk)
    else:
        raise ValueError(f"kind={kind!r}; choose from {KINDS}")
    body = tile[0] * tile[1] * i32                       # unpacked indices
    body += tile[0] * tile[1] * f32                      # dequantized tile
    return 2 * dma + body


def validate_block_config(kind: str, bm: int, bn: int, bk: int, bits: int,
                          k: int, *, order: str = "kd",
                          budget: int = VMEM_BUDGET) -> Dict[str, Any]:
    """Statically lint one block config; returns
    ``{"ok", "errors", "warnings", "vmem_bytes"}``.  ``errors`` are
    conditions the kernels reject (lane straddling) or Mosaic cannot fit
    (VMEM over budget); ``warnings`` are padding inefficiencies.
    """
    errors: List[str] = []
    warnings: List[str] = []
    lanes = 32 // bits
    if min(bm, bn, bk) < 1:
        errors.append(f"non-positive block ({bm},{bn},{bk})")
    if kind == "packed_matmul" and bk % lanes:
        errors.append(f"bk={bk} not a multiple of lanes={lanes} "
                      f"(bits={bits}): words straddle the k-block edge")
    if kind == "packed_matmul_t":
        if order == "kd" and bn % lanes:
            errors.append(f"bn={bn} not a multiple of lanes={lanes} "
                          f"(bits={bits}): V is the word-packed axis")
        if order == "row" and bk % lanes:
            errors.append(f"bk={bk} not a multiple of lanes={lanes} "
                          f"(bits={bits}): D is the word-packed axis")
    if kind == "gather" and bk % lanes:
        errors.append(f"word row of {bk} features not a multiple of "
                      f"lanes={lanes}")
    if kind != "gather":
        if bm % 8:
            warnings.append(f"bm={bm} not a multiple of the f32 sublane "
                            f"tile (8) — Mosaic pads the activation block")
        for name, v in (("bn", bn), ("bk", bk)):
            if v % 128:
                warnings.append(f"{name}={v} not 128-lane aligned — "
                                f"padded tiles waste VPU/MXU width")
    vmem = estimate_vmem_bytes(kind, bm, bn, bk, bits, k, order=order)
    if vmem > budget:
        errors.append(f"~{vmem / 2**20:.1f} MiB/step exceeds the "
                      f"{budget / 2**20:.1f} MiB VMEM budget "
                      f"(core has {VMEM_BYTES / 2**20:.0f} MiB)")
    elif vmem > 0.8 * budget:
        warnings.append(f"~{vmem / 2**20:.1f} MiB/step is within 20% of "
                        f"the {budget / 2**20:.1f} MiB VMEM budget")
    return {"ok": not errors, "errors": errors, "warnings": warnings,
            "vmem_bytes": vmem}


def _leaf_block_configs(leaf: str, lay: PackedLayout
                        ) -> Iterable[Dict[str, Any]]:
    """Every (kind, blocks) the dispatch layer could pick for this leaf
    at the serve M values."""
    if lay.shape is not None:
        return                       # dequant-then-dot route — no kernel
    if lay.order == "row":
        # Embedding serving layout: fused gather (whole packed row per
        # token) + the row-order transposed LM-head route (tied models).
        yield {"kind": "gather", "blocks": (1, 1, lay.n),
               "m": 1, "order": "row"}
        for m in SERVE_M:
            bm, bn, bk = dispatch.packed_block_sizes_t(
                m, lay.n, lay.kd, lay.bits, "row")
            yield {"kind": "packed_matmul_t", "blocks": (bm, bn, bk),
                   "m": m, "order": "row"}
    else:
        for m in SERVE_M:
            bm, bn, bk = dispatch.packed_block_sizes(m, lay.kd, lay.n,
                                                     lay.bits)
            yield {"kind": "packed_matmul", "blocks": (bm, bn, bk),
                   "m": m, "order": "kd"}


def audit_block_space(protected: Dict[str, dict]) -> Dict[str, Any]:
    """Sweep every block config reachable from the autotune surface.

    ``protected`` is :func:`repro.analysis.graph.protected_leaves`
    output.  Covers (a) each autotune-table entry verbatim (both the
    forward and transposed interpretations it serves) and (b) the
    heuristic's picks for every packed leaf at the serve M values.
    Returns ``{"rows", "violations"}``; a violation is any config with
    ``errors`` — a table entry or heuristic output the kernels would
    reject or Mosaic could not fit.
    """
    jobs: List[Dict[str, Any]] = []
    for (m, kd, n, bits), blocks in dispatch.packed_block_table().items():
        jobs.append({"kind": "packed_matmul", "blocks": blocks, "m": m,
                     "order": "kd", "bits": bits, "k": 1 << bits,
                     "source": f"table[{m},{kd},{n},{bits}]"})
        lanes = 32 // bits
        bm, bn, bk = blocks
        bn_t = max(lanes, bn // lanes * lanes)   # packed_block_sizes_t
        jobs.append({"kind": "packed_matmul_t", "blocks": (bm, bn_t, bk),
                     "m": m, "order": "kd", "bits": bits, "k": 1 << bits,
                     "source": f"table[{m},{kd},{n},{bits}]:t"})
    for leaf, info in sorted(protected.items()):
        lay = info["layout"]
        for job in _leaf_block_configs(leaf, lay):
            job.update(bits=lay.bits, k=lay.k, source=leaf)
            jobs.append(job)

    rows: List[Dict[str, Any]] = []
    violations: List[Dict[str, str]] = []
    for job in jobs:
        bm, bn, bk = job["blocks"]
        res = validate_block_config(job["kind"], bm, bn, bk, job["bits"],
                                    job["k"], order=job["order"])
        rows.append({**job, **res})
        for err in res["errors"]:
            violations.append({
                "check": "vmem-blocks", "subject": job["source"],
                "detail": f"{job['kind']} blocks ({bm},{bn},{bk}) at "
                          f"M={job['m']}: {err}"})
    return {"rows": rows, "violations": violations}


def block_table_entries() -> Dict[Tuple[int, int, int, int],
                                  Tuple[int, int, int]]:
    """Re-export of the dispatch autotune table (audit CLI convenience)."""
    return dispatch.packed_block_table()


# ---------------------------------------------------------------------------
# Paged-attention / page-gather route (dispatch._PAGED_BLOCK_TABLE)
# ---------------------------------------------------------------------------

def estimate_paged_vmem_bytes(kind: str, feat: int, page_size: int,
                              token_tile: int, bits: int) -> int:
    """Per-grid-step VMEM bytes a paged kernel asks Mosaic to fit.

    Mirrors the BlockSpecs in ``kernels/paged_attention.py``: per step
    one ``token_tile``-token KV tile per cached tensor is DMA'd (×2 for
    double buffering) — dense f32 rows, or packed uint32 words plus the
    per-page codebooks when ``bits`` — and the quant kernel bodies
    create the unpacked index tile + the dequantized f32 tile.  The
    query side (q/out blocks + m/l/acc online-softmax scratch) is bounded by
    :data:`PAGED_Q_SIDE_FLOATS` rather than threaded per-config — it is
    token-tile independent and small against the budget.
    """
    f32 = u32 = i32 = 4
    bt = token_tile
    n_tensors = 1 if kind == "gather" else 2      # gather: one pool
    if bits:
        lanes = kvquant.kv_lanes(bits)
        k = kvquant.kv_entries(bits)
        # per-(token, head) rows pack independently; ceil over the whole
        # feature row is a faithful upper bound for the committed shapes
        words = -(-feat // lanes)
        kv_tile = bt * words * u32 + k * f32      # word tile + codebook
        body = n_tensors * (bt * feat * i32       # unpacked index tile
                            + bt * feat * f32)    # dequantized KV tile
    else:
        kv_tile = bt * feat * f32
        body = 0
    dma = n_tensors * kv_tile
    if kind == "gather":
        dma += page_size * feat * f32             # whole-page out block
        q_side = 0
    else:
        # logits + probs tiles ([heads, bt], heads ≤ 128) and the
        # query-side blocks/scratch upper bound
        body += 2 * 128 * bt * f32
        q_side = 7 * PAGED_Q_SIDE_FLOATS * f32    # q, out (×2 ea) + m/l/acc
    return 2 * dma + body + q_side


def validate_paged_block_config(kind: str, feat: int, page_size: int,
                                token_tile: int, bits: int, *,
                                budget: int = VMEM_BUDGET
                                ) -> Dict[str, Any]:
    """Statically lint one paged-route token-tile config; same contract
    as :func:`validate_block_config` — ``errors`` are what the ops layer
    rejects (non-divisor tiles) or Mosaic cannot fit."""
    errors: List[str] = []
    warnings: List[str] = []
    if kind not in PAGED_KINDS:
        errors.append(f"kind={kind!r}; choose from {PAGED_KINDS}")
        return {"ok": False, "errors": errors, "warnings": warnings,
                "vmem_bytes": 0}
    if bits and bits not in kvquant.KV_BITS_CHOICES:
        errors.append(f"kv_bits={bits} not in {kvquant.KV_BITS_CHOICES}")
        return {"ok": False, "errors": errors, "warnings": warnings,
                "vmem_bytes": 0}
    if token_tile < 1:
        errors.append(f"non-positive token_tile {token_tile}")
    elif page_size % token_tile:
        errors.append(f"token_tile={token_tile} does not divide "
                      f"page_size={page_size} — the kernels' grid "
                      f"(pages × tiles/page) would drop tokens")
    if feat % 128:
        warnings.append(f"feat={feat} not 128-lane aligned — Mosaic pads "
                        f"the KV tile's trailing dim")
    vmem = estimate_paged_vmem_bytes(kind, feat, page_size,
                                     max(token_tile, 1), bits)
    if vmem > budget:
        errors.append(f"~{vmem / 2**20:.1f} MiB/step exceeds the "
                      f"{budget / 2**20:.1f} MiB VMEM budget "
                      f"(core has {VMEM_BYTES / 2**20:.0f} MiB)")
    elif vmem > 0.8 * budget:
        warnings.append(f"~{vmem / 2**20:.1f} MiB/step is within 20% of "
                        f"the {budget / 2**20:.1f} MiB VMEM budget")
    return {"ok": not errors, "errors": errors, "warnings": warnings,
            "vmem_bytes": vmem}


def audit_paged_block_space() -> Dict[str, Any]:
    """Sweep every committed ``dispatch._PAGED_BLOCK_TABLE`` entry — the
    paged-route analogue of :func:`audit_block_space`.  A bad token tile
    otherwise only fails at Mosaic compile time on a TPU."""
    rows: List[Dict[str, Any]] = []
    violations: List[Dict[str, str]] = []
    for (kind, feat, page, bits), tile in sorted(
            dispatch.paged_block_table().items()):
        source = f"paged_table[{kind},{feat},{page},{bits}]"
        res = validate_paged_block_config(kind, feat, page, tile, bits)
        rows.append({"kind": kind, "feat": feat, "page_size": page,
                     "bits": bits, "token_tile": tile, "source": source,
                     **res})
        for err in res["errors"]:
            violations.append({
                "check": "vmem-blocks", "subject": source,
                "detail": f"paged {kind} token_tile={tile}: {err}"})
    return {"rows": rows, "violations": violations}


# ---------------------------------------------------------------------------
# Blockwise-prefill route (dispatch._PREFILL_BLOCK_TABLE)
# ---------------------------------------------------------------------------

PREFILL_KINDS = ("dense", "quant")

# Bounds on the chunk-side operands of the blockwise-prefill kernel:
# every committed path partitions prompts into blocks of ≤ 64 new tokens
# (``transformer.DEFAULT_PREFILL_BLOCK`` and the engine's default
# ``prefill_chunk``), and the committed configs stay ≤ 32 query heads.
# The q/out blocks and the online-softmax scratch scale with these, not
# with the prompt length — that flatness is the kernel's whole point,
# and the estimate below proves it per table entry.
PREFILL_CHUNK_BOUND = 64
PREFILL_HEADS_BOUND = 32


def estimate_prefill_vmem_bytes(kind: str, feat: int, token_tile: int,
                                bits: int = 0) -> int:
    """Per-grid-step VMEM bytes a blockwise-prefill kernel asks Mosaic
    to fit.

    Mirrors the BlockSpecs in ``kernels/blockwise_prefill.py``: per step
    one ``token_tile`` K tile and one V tile are DMA'd (×2 double
    buffering) — dense rows, or packed uint32 words plus a single page's
    codebooks when ``bits`` — while the q/out blocks and the m/l/acc
    flash scratch are chunk-sized and bounded by
    :data:`PREFILL_CHUNK_BOUND` × :data:`PREFILL_HEADS_BOUND` (worst
    case ``rep == 1``: every query head has its own KV head).  Prompt
    length never appears: the footprint is flat in S.
    """
    f32 = u32 = i32 = 4
    bt = token_tile
    c, h = PREFILL_CHUNK_BOUND, PREFILL_HEADS_BOUND
    kv = h
    if kind == "quant" and bits:
        lanes = kvquant.kv_lanes(bits)
        kent = kvquant.kv_entries(bits)
        words = -(-feat // lanes)
        kv_tile = bt * kv * words * u32 + kent * f32
        # unpack index tile + dequantized f32 tile, for K and for V
        body = 2 * bt * kv * feat * (i32 + f32)
    else:
        kv_tile = bt * kv * feat * f32
        body = 0
    dma = 2 * kv_tile                        # K tile + V tile
    q_out = 2 * 2 * c * h * feat * f32       # q and out blocks, ×2 buffered
    scratch = h * c * (2 + feat) * f32       # m/l/acc online-softmax carry
    return 2 * dma + body + q_out + scratch


def validate_prefill_block_config(kind: str, feat: int, token_tile: int,
                                  bits: int = 0, *,
                                  budget: int = VMEM_BUDGET
                                  ) -> Dict[str, Any]:
    """Statically lint one blockwise-prefill token-tile entry; same
    contract as :func:`validate_paged_block_config`.  The quant route
    clamps tiles to page-size divisors at dispatch time, so divisibility
    is not an error here — only footprint and basic hygiene are."""
    errors: List[str] = []
    warnings: List[str] = []
    if kind not in PREFILL_KINDS:
        errors.append(f"kind={kind!r}; choose from {PREFILL_KINDS}")
        return {"ok": False, "errors": errors, "warnings": warnings,
                "vmem_bytes": 0}
    if bits and bits not in kvquant.KV_BITS_CHOICES:
        errors.append(f"kv_bits={bits} not in {kvquant.KV_BITS_CHOICES}")
        return {"ok": False, "errors": errors, "warnings": warnings,
                "vmem_bytes": 0}
    if token_tile < 1:
        errors.append(f"non-positive token_tile {token_tile}")
    elif token_tile % 8:
        warnings.append(f"token_tile={token_tile} not a multiple of the "
                        f"f32 sublane tile (8) — Mosaic pads the KV tile")
    if feat % 128:
        warnings.append(f"feat={feat} not 128-lane aligned — Mosaic pads "
                        f"the KV tile's trailing dim")
    vmem = estimate_prefill_vmem_bytes(kind, feat, max(token_tile, 1),
                                       bits)
    if vmem > budget:
        errors.append(f"~{vmem / 2**20:.1f} MiB/step exceeds the "
                      f"{budget / 2**20:.1f} MiB VMEM budget "
                      f"(core has {VMEM_BYTES / 2**20:.0f} MiB)")
    elif vmem > 0.8 * budget:
        warnings.append(f"~{vmem / 2**20:.1f} MiB/step is within 20% of "
                        f"the {budget / 2**20:.1f} MiB VMEM budget")
    return {"ok": not errors, "errors": errors, "warnings": warnings,
            "vmem_bytes": vmem}


def audit_prefill_block_space() -> Dict[str, Any]:
    """Sweep every committed ``dispatch._PREFILL_BLOCK_TABLE`` entry —
    quant entries at every supported ``kv_bits`` (the table doesn't key
    on bits; the worst case must still fit)."""
    rows: List[Dict[str, Any]] = []
    violations: List[Dict[str, str]] = []
    for (kind, feat), tile in sorted(dispatch.prefill_block_table().items()):
        source = f"prefill_table[{kind},{feat}]"
        sweep = kvquant.KV_BITS_CHOICES if kind == "quant" else (0,)
        for bits in sweep:
            res = validate_prefill_block_config(kind, feat, tile, bits)
            rows.append({"kind": kind, "feat": feat, "bits": bits,
                         "token_tile": tile, "source": source, **res})
            for err in res["errors"]:
                violations.append({
                    "check": "vmem-blocks", "subject": source,
                    "detail": f"prefill {kind} token_tile={tile} "
                              f"(bits={bits}): {err}"})
    return {"rows": rows, "violations": violations}
