"""Fused MHA-Forward Pallas TPU kernel (paper §3.2, adapted to MXU/VMEM).

One `pl.pallas_call` computes ``O = dropout(softmax(QKᵀ·scale))·V`` without ever
writing S or P to HBM — the paper's 3-reads + 1-write I/O profile.  The Volta
warp mechanics (m8n8k4 MMA, register layout transform between the two matmuls)
are replaced by their TPU-native equivalents:

* grid = (batch, q_head, q_block, kv_block); the kv_block dim is sequential
  ("arbitrary"), so the online-softmax state lives in VMEM scratch across
  iterations — the role the paper's registers/SRAM play on Volta.  Tile
  edges follow the shape unless the caller gives them (kernels/schedule.py:
  up to 1024 tokens, so a 16k-token row issues 16×16 steps a head, not
  128×128).
* the S→P→(P·V) chain happens inside one kernel body; Mosaic owns the VREG
  relayout between the two `jnp.dot`s (the paper's warp-level layout transform).
* ``acc_dtype`` selects bf16-ACC / f32-ACC matmul accumulation
  (paper's FP16-ACC / FP32-ACC). Softmax state is always f32 (paper §3.2.1).
* the block schedule follows the mask (kernels/schedule.py): each q block's
  needed kv blocks form a range ``[lo, hi]``, scalar-prefetched; the K/V and
  kv-segment index maps clamp the kv index into it, so a step outside the
  range repeats the block already in VMEM and copies nothing, and
  ``pl.when`` skips its maths (the paper's thread-block early exit) as it
  does for any pair the causal, window, padding or segment test rejects.
* dropout masks are regenerated from element coordinates (kernels/rng.py), so
  the backward recompute sees identical masks with zero HBM mask traffic.

Segment-packed (varlen) batches: ``segment_ids [B, Skv]`` gives each kv token a
segment id; a token attends only within its own segment (negative ids mark
padding that attends to nothing and is attended by nothing).  The per-token ids
stream in as VMEM blocks aligned with the q/kv tiles, while per-block segment
min/max (of the valid ids; padding counts for none) arrive via
scalar-prefetch, so the schedule also skips blocks whose segment ranges cannot
intersect — the same ragged-skip pattern as ``kv_len`` in kernels/decode.py.
The min/max interval test is exact-safe for arbitrary ids (equal ids imply
overlapping ranges) and tight for the packed layout where ids are
non-decreasing along the sequence.

Block layouts obey Mosaic's tiling rule: the last two dims of every block are
divisible by (8, 128) or equal the array's.  So per-row values are lane-dense:
``lse`` is written as ``[B, Hq, Sq, LANES]`` (every lane holds the row's
value; the wrapper returns lane 0), q segment ids stream as ``[B, Sq, LANES]``
and kv segment ids as ``[B, SUBLANES, Skv]`` — the layout of upstream JAX's
Pallas TPU flash attention.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.online_softmax import NEG_INF
from repro.kernels import rng, schedule
from repro.kernels.common import LANES, SUBLANES, mosaic_kwargs, online_fold


def _fwd_kernel(*refs, scale: float, causal: bool, window: Optional[int],
                dropout_rate: float,
                block_q: int, block_kv: int, sq_real: int, skv_real: int,
                acc_dtype, segments: bool):
    # scalar prefetch: seed, lo and hi (read by the index maps alone), and
    # with segments the four segment bounds
    seed_ref = refs[0]
    if segments:
        bounds = refs[3:7]
        (q_ref, k_ref, v_ref, qseg_ref, kseg_ref,               # inputs
         o_ref, lse_ref,                                        # outputs
         acc_ref, m_ref, l_ref) = refs[7:]                      # VMEM scratch
    else:
        bounds = None
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         acc_ref, m_ref, l_ref) = refs[3:]
    b, h, iq, ik = (pl.program_id(i) for i in range(4))
    nk = pl.num_programs(3)
    q_offset = skv_real - sq_real          # q tokens are the suffix of kv
    q_start = iq * block_q + q_offset      # global position of first q row
    kv_start = ik * block_kv

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # ---- block-level early exit: the schedule's own test (steps outside
    # the needed range hold a repeated K/V block and do no compute) ----
    needed = schedule.kernel_needed(
        b, iq, ik, bounds, block_q=block_q, block_kv=block_kv,
        sq_real=sq_real, skv_real=skv_real, causal=causal, window=window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]                                     # [bq, D]
        k = k_ref[0, 0]                                     # [bkv, D]
        v = v_ref[0, 0]                                     # [bkv, D]
        # First matmul (S = Q Kᵀ) with selectable accumulate precision.
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=acc_dtype)
        s = s.astype(jnp.float32) * scale                   # softmax math in f32

        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kp = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        allowed = None
        if causal:
            allowed = kp <= qp
        if window is not None:
            w_ok = kp > qp - window
            allowed = w_ok if allowed is None else (allowed & w_ok)
        if skv_real % block_kv:
            pad_ok = kp < skv_real
            allowed = pad_ok if allowed is None else (allowed & pad_ok)
        if segments:
            q_seg = qseg_ref[0][:, :1]                      # [bq, 1]
            kv_seg = kseg_ref[0][:1, :]                     # [1, bkv]
            seg_ok = (q_seg == kv_seg) & (q_seg >= 0)
            allowed = seg_ok if allowed is None else (allowed & seg_ok)
        if allowed is not None:
            s = jnp.where(allowed, s, NEG_INF)

        # ---- online softmax update (paper Eq. 3): the shared fold, with
        # dropout hooked between the l update (pre-dropout probabilities,
        # matching the reference softmax) and the P·V matmul ----
        p_transform = None
        if dropout_rate > 0.0:
            def p_transform(p):
                keep = rng.dropout_keep_mask(dropout_rate, seed_ref[0], b, h,
                                             qp, kp)
                return jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        online_fold(s, v, acc_ref, m_ref, l_ref, acc_dtype=acc_dtype,
                    p_transform=p_transform)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]                                      # [bq, LANES]
        l_safe = jnp.where(l == 0.0, 1.0, l)                # fully-masked rows → 0
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l_safe)        # lane-dense rows


def flash_fwd(q, k, v, *, causal: bool = False, window: Optional[int] = None,
              scale: Optional[float] = None, dropout_rate: float = 0.0,
              dropout_seed: int = 0, segment_ids=None, acc_dtype=jnp.float32,
              block_q: Optional[int] = None, block_kv: Optional[int] = None,
              interpret: bool = False):
    """Returns (o [B,Hq,Sq,D], lse [B,Hq,Sq] f32). Pads seq dims to block multiples.

    block_q / block_kv: tile edges; None picks them from the shape
    (``schedule.tile``).

    segment_ids: optional [B, Skv] int32 — per-token segment ids over the kv
    sequence (q tokens are its suffix). Attention is masked across segments;
    negative ids mark padding rows/keys that attend to nothing.
    """
    b, hq, sq_real, d = q.shape
    _, hkv, skv_real, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale

    row_bytes = d * jnp.dtype(q.dtype).itemsize
    block_q = schedule.tile(sq_real, block_q, row_bytes)
    block_kv = schedule.tile(skv_real, block_kv, row_bytes)
    sq = pl.cdiv(sq_real, block_q) * block_q
    skv = pl.cdiv(skv_real, block_kv) * block_kv
    if sq != sq_real:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq - sq_real), (0, 0)))
    if skv != skv_real:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv - skv_real), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv - skv_real), (0, 0)))

    nq, nk = sq // block_q, skv // block_kv
    segments = segment_ids is not None
    mask = dict(block_q=block_q, block_kv=block_kv, sq_real=sq_real,
                skv_real=skv_real, causal=causal, window=window)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, dropout_rate=dropout_rate,
        acc_dtype=acc_dtype, segments=segments, **mask)

    kwargs = mosaic_kwargs(
        interpret, ("parallel", "parallel", "parallel", "arbitrary"))

    # K/V blocks follow the schedule: each q block's kv index is clamped
    # into its needed range, so a step outside it fetches nothing new
    def kv_map(b_, h, iq, ik, *prefetch):
        return (b_, h // group, schedule.clamped(b_, iq, ik, *prefetch), 0)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda b_, h, iq, ik, *_: (b_, h, iq, 0)),
        pl.BlockSpec((1, 1, block_kv, d), kv_map),
        pl.BlockSpec((1, 1, block_kv, d), kv_map),
    ]
    inputs = (q, k, v)
    bounds = ()
    if segments:
        q_seg, kv_seg, bounds = schedule.pad_segments(
            segment_ids, b, sq_real, skv_real, sq, skv, block_q, block_kv)
        inputs = inputs + (q_seg, kv_seg)
        in_specs += [
            pl.BlockSpec((1, block_q, LANES),
                         lambda b_, h, iq, ik, *_: (b_, iq, 0)),
            pl.BlockSpec((1, SUBLANES, block_kv),
                         lambda b_, h, iq, ik, *r:
                         (b_, 0, schedule.clamped(b_, iq, ik, *r))),
        ]
    needed = schedule.needed_blocks(b, nq, nk, bounds=bounds or None, **mask)
    seed = jnp.atleast_1d(jnp.asarray(dropout_seed, jnp.int32))
    prefetch = (seed, *schedule.block_range(needed, axis=2), *bounds)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, hq, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, iq, ik, *_: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b_, h, iq, ik, *_: (b_, h, iq, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        **kwargs,
    )(*prefetch, *inputs)

    lse = lse[..., 0]
    if sq != sq_real:
        o = o[:, :, :sq_real]
        lse = lse[:, :, :sq_real]
    return o, lse
