"""The training kernels' block schedule (kernels/schedule.py) against brute
force: on the benchmark's own packed layouts, the block pairs it lets compute
are exactly those that hold an allowed (q, kv) token pair."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import gen  # noqa: E402
from repro.kernels import schedule  # noqa: E402


def _layouts(mix_name):
    mix = gen.load_mix(mix_name)
    rng = np.random.default_rng(0)
    return [gen.pack_batch(rows, rng, 2, mix["row_len"])["segment_ids"]
            for rows in gen.train_layouts(mix)]


def brute_needed_pairs(seg, block):
    """Block pairs of one [B, S] layout holding a causal same-document token
    pair, token by token (one q block against every key at a time)."""
    b, s = seg.shape
    n = -(-s // block)
    ids = np.full((b, n * block), -1, np.int32)
    ids[:, :s] = seg
    pos = np.arange(n * block)
    total = 0
    for row in ids:
        for iq in range(n):
            q = slice(iq * block, (iq + 1) * block)
            allowed = ((row[q, None] == row[None, :]) & (row[q, None] >= 0)
                       & (pos[None, :] <= pos[q, None]))
            total += int(allowed.reshape(block, n, block).any(axis=(0, 2))
                         .sum())
    return total


@pytest.mark.parametrize("mix,block", [("train-longdoc", None),
                                       ("train-shortdoc", None),
                                       ("train-shortdoc", 128)])
def test_schedule_computes_exactly_the_needed_pairs(mix, block):
    layouts = _layouts(mix)
    s = layouts[0].shape[1]
    tile = schedule.tile(s, block, 128)
    for seg in layouts:
        issued, computing = schedule.step_counts(seg, heads=32, causal=True,
                                                 block_q=block,
                                                 block_kv=block)
        assert issued == 32 * seg.shape[0] * (-(-s // tile)) ** 2
        assert computing == 32 * brute_needed_pairs(seg, tile)


@pytest.mark.parametrize("s,row_bytes,expected", [
    (64, 128, 64), (1024, 128, 1024), (1200, 128, 640), (2048, 128, 1024),
    (2100, 128, 768), (16384, 128, 1024), (3, 128, 8),
    (2048, 256, 1024), (2048, 512, 512), (1200, 1024, 512)])
def test_shape_chosen_tile(s, row_bytes, expected):
    t = schedule.tile(s, None, row_bytes)
    assert t == expected
    target = schedule.TILE if row_bytes <= schedule.ROW_BYTES \
        else schedule.WIDE_TILE
    if t < s:                      # several blocks: lane-aligned, least pad
        assert t % 128 == 0 and t <= target
        assert -(-s // t) == -(-s // target)


def test_ranges_cover_needed_and_padding_needs_nothing():
    """[lo, hi] is the hull of each row's needed blocks; a block of padding
    alone needs no step, and its row gets the empty range [0, 0]."""
    seg = np.array([[0] * 100 + [1] * 60 + [-1] * 96], np.int32)   # 256
    b, s = seg.shape
    ids = seg.reshape(b, -1)
    bounds = schedule.segment_bounds(ids, 64) * 2
    needed = schedule.needed_blocks(b, 4, 4, bounds=bounds, block_q=64,
                                    block_kv=64, sq_real=s, skv_real=s,
                                    causal=True, window=None)
    lo, hi = (np.asarray(x) for x in schedule.block_range(needed, axis=2))
    assert needed[0, 3].sum() == 0 and needed[0, :, 3].sum() == 0
    assert lo[0].tolist() == [0, 0, 1, 0] and hi[0].tolist() == [0, 1, 2, 0]
    for iq in range(3):
        nz = np.flatnonzero(needed[0, iq])
        assert (nz.min(), nz.max()) == (lo[0, iq], hi[0, iq])
