"""The benchmark's operation and byte counts against brute-force enumeration."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import counts  # noqa: E402

DIMS = counts.Dims(layers=3, d_model=64, heads=4, kv_heads=2, head_dim=16,
                   d_ff=96, vocab=101)


def brute_pairs(seg_lens):
    """Causal query-key pairs within each document, one by one."""
    seg = np.repeat(np.arange(len(seg_lens)), seg_lens)
    n = 0
    for i in range(len(seg)):
        for j in range(i + 1):
            n += seg[i] == seg[j]
    return n


@pytest.mark.parametrize("seg_lens", [[1], [7], [3, 5, 1], [16, 16],
                                      [2, 9, 4, 4, 1]])
def test_causal_pairs_match_enumeration(seg_lens):
    assert counts.causal_pairs(seg_lens) == brute_pairs(seg_lens)


@pytest.mark.parametrize("seg_lens", [[5], [3, 5, 1], [8, 8, 8]])
def test_attention_flops_count_each_pair_per_head(seg_lens):
    pairs = brute_pairs(seg_lens)
    fwd, _ = counts.attn_fwd(DIMS, seg_lens)
    bwd, _ = counts.attn_bwd(DIMS, seg_lens)
    # QK^T and PV: 2 flops per multiply-add, D multiply-adds each, per head
    assert fwd == 2 * 2 * DIMS.head_dim * DIMS.heads * pairs
    # dV, dP, dQ, dK; the recomputed QK^T does not count
    assert bwd == 2 * fwd


def test_attention_bytes_move_each_tensor_once():
    t = 12
    _, fwd = counts.attn_fwd(DIMS, [5, 7])
    q = t * DIMS.heads * DIMS.head_dim * 2
    kv = t * DIMS.kv_heads * DIMS.head_dim * 2
    lse = t * DIMS.heads * 4
    assert fwd == q + 2 * kv + q + lse           # Q, K, V in; O, lse out
    _, bwd = counts.attn_bwd(DIMS, [5, 7])
    assert bwd == (q + 2 * kv + q + 2 * lse) + (q + 2 * kv)


def test_counts_ignore_block_sizes_and_document_order():
    assert counts.attn_fwd(DIMS, [3, 9, 4]) == counts.attn_fwd(DIMS, [9, 4, 3])


def test_paged_decode_counts_live_keys_only():
    flops, nbytes = counts.paged_decode(DIMS, [10, 0, 3])
    assert flops == 4 * DIMS.head_dim * DIMS.heads * 13
    assert nbytes == 13 * 2 * DIMS.kv_heads * DIMS.head_dim * 2 \
        + 2 * 2 * DIMS.heads * DIMS.head_dim * 2


def test_prompt_lengths_of_a_packed_row():
    seg = np.array([0, 0, 0, 1, 1, 2, -1, -1])
    assert counts.prompt_lengths(seg) == [3, 2, 1]
    assert counts.prompt_lengths(np.full(4, -1)) == []


def test_model_flops():
    per_tok = DIMS.layers * DIMS.layer_matmul_params + DIMS.head_params
    rows = [[4, 4], [8]]
    assert counts.train_step_flops(DIMS, rows) == 6 * 16 * per_tok + \
        12 * DIMS.head_dim * DIMS.heads * DIMS.layers * brute_pairs([4, 4, 8])
    # prefill: the head only on each prompt's last position
    assert counts.prefill_flops(DIMS, [4, 8]) == \
        2 * 12 * DIMS.layers * DIMS.layer_matmul_params \
        + 2 * 2 * DIMS.head_params \
        + 4 * DIMS.head_dim * DIMS.heads * DIMS.layers * brute_pairs([4, 8])
    assert counts.decode_flops(DIMS, [5, 0]) == 2 * per_tok + \
        4 * DIMS.head_dim * DIMS.heads * 5 * DIMS.layers


def test_roofline_share_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_share(100.0, 1.0, 2.0, peaks) == (50.0, "flops")
    assert counts.roofline_share(1.0, 10.0, 4.0, peaks) == (25.0, "bytes")
