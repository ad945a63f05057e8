"""Block schedule of the training attention kernels (flash_fwd, flash_bwd).

Each of the three kernels runs a grid ``(B, Hq, outer, inner)`` whose inner
axis is sequential: it visits every block of the other sequence for one
block of its own.  A grid step costs a fixed fraction of a microsecond on a
TPU, whether it computes or not, so the schedule decides the kernels' time
as much as the maths in a step does.  Two choices make it:

* **Tiles follow the shape.** ``tile`` picks a block edge from the sequence
  length and the width of a row: ``TILE`` tokens or fewer (``WIDE_TILE`` for
  wide rows), a multiple of 128 when there are several blocks, as few blocks
  as that allows and the least padding among them; one block holds a short
  sequence whole.  A caller's explicit block
  size is honoured as given.
* **The range follows the mask.** ``needed_blocks`` tests every (q block,
  kv block) pair against the causal, window, padding and segment masks, on
  per-block facts only: positions and each block's least and greatest
  segment id.  ``block_range`` reduces that to ``[lo, hi]``, the first and
  last needed block of each row of the grid.  The kernels' K/V (or, in the
  dK/dV pass, Q-side) index maps clamp the inner index into that range, so
  a step outside it asks for the block its neighbour already holds: the
  pipeline copies nothing, and the step's ``pl.when`` skips the maths.

``pair_needed`` is written with operators alone, so the kernels evaluate it
on scalars read from SMEM, the wrappers on broadcast ``jnp`` arrays and the
benchmark's reports on ``numpy`` arrays, all with the same answer.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.kernels.common import LANES, SUBLANES

# Largest block edge the shape-chosen tiles take, and the edge for rows wider
# than ROW_BYTES (D × itemsize), whose blocks and score tile would not fit
# the compiler's VMEM at 1024 (f32, D=256).  Chosen on a TPU v5e at the
# benchmark's shapes over 256, 512 and 1024 (PERF.md, Findings).
TILE, WIDE_TILE, ROW_BYTES = 1024, 512, 256
# No segment id: the least id of a block with no valid token.
_NO_SEG = np.iinfo(np.int32).max


def tile(s: int, block: Optional[int], row_bytes: int) -> int:
    """Block edge for a sequence of ``s`` tokens whose rows (one head's
    q, k or v vector) hold ``row_bytes``.

    ``block`` given: that edge, capped at the sequence (but at least 8).
    Otherwise the fewest blocks of at most ``TILE`` tokens (``WIDE_TILE``
    for rows wider than ``ROW_BYTES``), each a multiple of 128 and as small
    as that count allows; one block when ``s`` fits.
    """
    if block is not None:
        return min(block, max(s, 8))
    target = TILE if row_bytes <= ROW_BYTES else WIDE_TILE
    if s <= target:
        return max(s, 8)
    n = -(-s // target)
    return -(-s // (n * 128)) * 128


def pair_needed(iq, ik, *, block_q: int, block_kv: int, sq_real: int,
                skv_real: int, causal: bool, window: Optional[int],
                seg=None):
    """Whether q block ``iq`` and kv block ``ik`` may hold an allowed pair.

    Conservative and exact-safe: a pair it rejects holds no allowed (q, kv)
    token pair.  ``seg`` is ``(q_min, q_max, kv_min, kv_max)``, the blocks'
    least and greatest valid segment ids (``segment_bounds``).  q tokens
    are the suffix of the kv sequence.
    """
    q_start = iq * block_q + (skv_real - sq_real)
    kv_start = ik * block_kv
    needed = (iq * block_q < sq_real) & (kv_start < skv_real)
    if causal:
        needed = needed & (kv_start <= q_start + block_q - 1)
    if window is not None:
        needed = needed & (kv_start + block_kv - 1 > q_start - window)
    if seg is not None:
        q_min, q_max, kv_min, kv_max = seg
        needed = needed & (kv_min <= q_max) & (kv_max >= q_min)
    return needed


def kernel_needed(b, iq, ik, bounds, **mask):
    """``pair_needed`` inside a kernel: ``bounds`` are the four
    scalar-prefetched segment-bound refs (or None without segments)."""
    seg = None
    if bounds is not None:
        q_min, q_max, kv_min, kv_max = bounds
        seg = (q_min[b, iq], q_max[b, iq], kv_min[b, ik], kv_max[b, ik])
    return pair_needed(iq, ik, seg=seg, **mask)


def segment_bounds(ids, block: int):
    """Least and greatest valid (non-negative) id of each block of ``ids``
    ``[B, n * block]``, as ``[B, n]`` arrays.  A block with no valid id gets
    ``(int32 max, -1)``, which no range intersects: padding attends nothing
    and is attended by nothing, so no block of it needs a step."""
    xp = jnp if isinstance(ids, jnp.ndarray) else np
    blocks = ids.reshape(ids.shape[0], -1, block)
    valid = blocks >= 0
    return (xp.where(valid, blocks, _NO_SEG).min(-1),
            xp.where(valid, blocks, -1).max(-1))


def padded_bounds(ids, sq_real: int, sq: int, skv: int, block_q: int,
                  block_kv: int):
    """The q ids (the suffix of ``ids`` [B, Skv]) and the kv ids, each
    padded with -1 to ``sq`` / ``skv``, and their per-block bounds
    ``(q_min, q_max, kv_min, kv_max)`` (jnp or numpy, as ``ids``)."""
    xp = jnp if isinstance(ids, jnp.ndarray) else np
    skv_real = ids.shape[1]
    q_ids = xp.pad(ids[:, skv_real - sq_real:], ((0, 0), (0, sq - sq_real)),
                   constant_values=-1)
    kv_ids = xp.pad(ids, ((0, 0), (0, skv - skv_real)), constant_values=-1)
    return q_ids, kv_ids, (segment_bounds(q_ids, block_q)
                           + segment_bounds(kv_ids, block_kv))


def pad_segments(segment_ids, b: int, sq_real: int, skv_real: int, sq: int,
                 skv: int, block_q: int, block_kv: int):
    """Per-token ids in the kernels' tile-legal layouts, and per-block bounds.

    Returns (q_seg [B, sq, LANES], kv_seg [B, SUBLANES, skv], bounds
    (q_min, q_max, kv_min, kv_max) each [B, n_blocks] int32).  Padding uses
    -1, which never matches (``seg >= 0`` in the kernels).
    """
    ids = jnp.asarray(segment_ids, jnp.int32)
    assert ids.shape == (b, skv_real), (
        f"segment_ids must be [B, Skv] = {(b, skv_real)}, got {ids.shape}")
    q_seg, kv_seg, bounds = padded_bounds(ids, sq_real, sq, skv, block_q,
                                          block_kv)
    q_seg = jnp.broadcast_to(q_seg[:, :, None], (b, sq, LANES))
    kv_seg = jnp.broadcast_to(kv_seg[:, None, :], (b, SUBLANES, skv))
    return q_seg, kv_seg, bounds


def needed_blocks(b: int, nq: int, nk: int, *, bounds=None, **mask):
    """``[B, nq, nk]`` bool: ``pair_needed`` of every block pair.  ``mask``
    holds ``pair_needed``'s keyword arguments; ``bounds`` are
    ``segment_bounds`` of the q and kv ids (jnp or numpy)."""
    xp = np if bounds is None or isinstance(bounds[0], np.ndarray) else jnp
    iq = xp.arange(nq)[None, :, None]
    ik = xp.arange(nk)[None, None, :]
    seg = None
    if bounds is not None:
        q_min, q_max, kv_min, kv_max = bounds
        seg = (q_min[:, :, None], q_max[:, :, None],
               kv_min[:, None, :], kv_max[:, None, :])
    needed = pair_needed(iq, ik, seg=seg, **mask)
    return xp.broadcast_to(needed, (b, nq, nk))


def block_range(needed, axis: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(lo, hi)`` int32: the first and last needed block along ``axis``
    (the grid's sequential axis) for each block of the other axis.  A row
    with no needed block gets ``[0, 0]``."""
    n = needed.shape[axis]
    hit = jnp.any(needed, axis=axis)
    lo = jnp.argmax(needed, axis=axis)
    hi = n - 1 - jnp.argmax(jnp.flip(needed, axis=axis), axis=axis)
    return (jnp.where(hit, lo, 0).astype(jnp.int32),
            jnp.where(hit, hi, 0).astype(jnp.int32))


def clamped(b, i, j, seed_ref, lo_ref, hi_ref, *_):
    """Index-map helper for a grid ``(b, h, i, j)`` with ``j`` sequential:
    ``j`` clamped into row ``i``'s needed range.  Takes the kernels'
    scalar-prefetch refs in their order: seed, lo, hi, segment bounds."""
    return jnp.minimum(jnp.maximum(j, lo_ref[b, i]), hi_ref[b, i])


def step_counts(segment_ids, *, heads: int, causal: bool,
                window: Optional[int] = None, sq: Optional[int] = None,
                block_q: Optional[int] = None, block_kv: Optional[int] = None,
                row_bytes: int = 128) -> Tuple[int, int]:
    """Grid steps one kernel call issues, and how many of them compute.

    ``segment_ids`` ``[B, Skv]`` (numpy); ``sq`` the query length (the kv
    length when None); tiles as the kernels choose them for ``row_bytes``
    (bf16, D=64 by default) unless given.  The same count holds for the
    forward and both backward passes.
    """
    ids = np.asarray(segment_ids, np.int32)
    b, skv_real = ids.shape
    sq_real = skv_real if sq is None else sq
    bq = tile(sq_real, block_q, row_bytes)
    bkv = tile(skv_real, block_kv, row_bytes)
    nq, nk = -(-sq_real // bq), -(-skv_real // bkv)
    _, _, bounds = padded_bounds(ids, sq_real, nq * bq, nk * bkv, bq, bkv)
    needed = needed_blocks(b, nq, nk, bounds=bounds, block_q=bq,
                           block_kv=bkv, sq_real=sq_real, skv_real=skv_real,
                           causal=causal, window=window)
    return b * heads * nq * nk, heads * int(needed.sum())
