"""Operations and bytes the algorithms need, from shapes and segment layouts.

Counts never depend on block sizes or on what a kernel actually computes: a
kernel that skips more masked blocks reads as a higher share of its roofline,
never as a different count.  Matmul FLOPs are 2 per multiply-add.

Attention (one layer, causal within each packed document of n tokens, which
holds n(n+1)/2 query-key pairs):
  forward   2 matmuls (QK^T, PV):          4 * D * Hq * pairs
  backward  4 matmuls (dV, dP, dQ, dK):    8 * D * Hq * pairs
            (the recomputed QK^T does not count)
Bytes, each tensor moved once between HBM and the chip:
  forward   reads Q, K, V; writes O and the f32 log-sum-exp of each row
  backward  reads Q, K, V, dO and the f32 lse and delta rows; writes dQ, dK, dV
  paged decode (one query token per row, against L cached keys)
            reads q and the L keys and values of each KV head; writes o
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, List, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class Dims:
    """The shapes that fix the work of one decoder model."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   dtype_bytes={"bfloat16": 2, "float32": 4}[c["torch_dtype"]])

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies in one layer: q, k, v, o projections
        and the gated MLP (gate, up, down)."""
        d, qd, kvd = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return d * qd + 2 * d * kvd + qd * d + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab


def prompt_lengths(seg) -> List[int]:
    """Lengths of the documents packed into one row of segment ids, in id
    order; negative ids are padding."""
    seg = np.asarray(seg).ravel()
    ids = seg[seg >= 0]
    return [int(n) for n in np.bincount(ids)[np.unique(ids)]] if ids.size \
        else []


def causal_pairs(seg_lens: Iterable[int]) -> int:
    """Query-key pairs of causal attention within each document."""
    return sum(int(n) * (int(n) + 1) // 2 for n in seg_lens)


def attn_fwd(dims: Dims, seg_lens: Sequence[int]):
    """(flops, bytes) of one layer's attention forward over these documents."""
    t = sum(int(n) for n in seg_lens)
    hq, hkv, hd, b = dims.heads, dims.kv_heads, dims.head_dim, dims.dtype_bytes
    flops = 4 * hd * hq * causal_pairs(seg_lens)
    nbytes = t * (2 * hq * hd + 2 * hkv * hd) * b + t * hq * 4
    return flops, nbytes


def attn_bwd(dims: Dims, seg_lens: Sequence[int]):
    """(flops, bytes) of one layer's attention backward (dQ, dK, dV)."""
    t = sum(int(n) for n in seg_lens)
    hq, hkv, hd, b = dims.heads, dims.kv_heads, dims.head_dim, dims.dtype_bytes
    flops = 8 * hd * hq * causal_pairs(seg_lens)
    reads = t * (2 * hq * hd + 2 * hkv * hd) * b + 2 * t * hq * 4
    writes = t * (hq * hd + 2 * hkv * hd) * b
    return flops, reads + writes


def paged_decode(dims: Dims, kv_lens: Sequence[int]):
    """(flops, bytes) of one layer's decode attention: one query token per
    live row, ``kv_lens`` keys each (rows with no keys are not work)."""
    hq, hkv, hd, b = dims.heads, dims.kv_heads, dims.head_dim, dims.dtype_bytes
    keys = sum(int(n) for n in kv_lens)
    rows = sum(1 for n in kv_lens if n > 0)
    flops = 4 * hd * hq * keys
    nbytes = keys * 2 * hkv * hd * b + rows * 2 * hq * hd * b
    return flops, nbytes


def train_step_flops(dims: Dims, rows: Sequence[Sequence[int]]) -> int:
    """Model FLOPs of one training step: 6 per weight per token for every
    matmul (LM head included), plus attention forward and backward.
    Recomputation does not count."""
    tokens = sum(sum(r) for r in rows)
    pairs = sum(causal_pairs(r) for r in rows)
    mm = 6 * tokens * (dims.layers * dims.layer_matmul_params
                       + dims.head_params)
    attn = 12 * dims.head_dim * dims.heads * pairs * dims.layers
    return mm + attn


def prefill_flops(dims: Dims, prompt_lens: Sequence[int]) -> int:
    """Model FLOPs of prefilling these prompts: every layer on every prompt
    token, causal attention within each prompt, and the LM head on the one
    position that yields each prompt's first token."""
    tokens = sum(int(n) for n in prompt_lens)
    return (2 * tokens * dims.layers * dims.layer_matmul_params
            + 2 * len(prompt_lens) * dims.head_params
            + 4 * dims.head_dim * dims.heads * causal_pairs(prompt_lens)
            * dims.layers)


def decode_flops(dims: Dims, kv_lens: Sequence[int]) -> int:
    """Model FLOPs of one decode step: each live row's token through every
    layer and the LM head, attending to its ``kv_len`` keys."""
    live = [int(n) for n in kv_lens if n > 0]
    return (2 * len(live) * (dims.layers * dims.layer_matmul_params
                             + dims.head_params)
            + 4 * dims.head_dim * dims.heads * sum(live) * dims.layers)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict):
    """(percent of the roofline, the bound that set it: "flops" or "bytes")."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
