"""Cells, configurations and traffic mixes, read from their files.

A configuration file (``bench/configs/<name>.json``) holds the model's
published sizes under their published keys, the serving set-up
(``serving``), the limit of the correctness check (``correct``), and
``source``, ``deployment``, ``reduced`` and ``assumed``.  Nothing here
imports the program: the reference and the weight generator read the
architecture from this file alone.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(name: str) -> Dict[str, Any]:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_file(name: str) -> Dict[str, Any]:
    return _load_json(os.path.join(BENCH_DIR, "configs", f"{name}.json"))


def traffic_file(name: str) -> Dict[str, Any]:
    return _load_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def metrics_for(cell: str, trace: bool) -> List[Dict[str, Any]]:
    """The metric entries of BENCHMARK.json that ``cell`` reports: its
    end-to-end metrics untraced, its per-layer metrics traced."""
    entries = benchmark()["per_layer" if trace else "end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """A llama-like decoder as a configuration file states it."""

    layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    qkv_bias: bool
    emb_scale: Optional[float]
    k: int                 # codebook entries of every packed leaf
    kv_bits: int           # 0: dense KV pages
    dtype: str             # served activation / dense-leaf dtype
    page_size: int
    n_slots: int
    max_seq: int
    prefill_chunk: int
    n_pages: int           # KV pages in the pool, shared by the slots

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "ModelSpec":
        if not c.get("tie_word_embeddings", False):
            raise ValueError("only tied-embedding models are described")
        if c.get("hidden_act") != "silu":
            raise ValueError("only gated silu MLPs are described")
        s = c["serving"]
        heads = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=heads, n_kv=c["num_key_value_heads"],
                   head_dim=c.get("head_dim", c["hidden_size"] // heads),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   qkv_bias=bool(c["qkv_bias"]),
                   emb_scale=(float(c["scale_emb"]) if "scale_emb" in c
                              else None),
                   k=s["codebook_entries"], kv_bits=s["kv_bits"],
                   dtype=s["dtype"], page_size=s["page_size"],
                   n_slots=s["n_slots"], max_seq=s["max_seq"],
                   prefill_chunk=s["prefill_chunk"],
                   n_pages=s.get("n_pages", s["n_slots"] * -(
                       -s["max_seq"] // s["page_size"])))

    @property
    def bits(self) -> int:
        """Bits per packed index (eq. 14): ceil(log2 K)."""
        return max(1, (self.k - 1).bit_length())

    @property
    def lanes(self) -> int:
        return 32 // self.bits
