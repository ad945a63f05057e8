"""Fused MHA-Backward Pallas TPU kernels (paper §3.3, adapted to TPU).

The paper implements the backward as ONE kernel: each thread block owns a KV
block, iterates over Q blocks, accumulates dK/dV locally and scatters dQ with
HBM **atomic adds**.  TPUs have no HBM atomics; the TPU-idiomatic equivalent
(see docs/architecture.md) is a **dual-pass** design where each pass owns the
tensor it accumulates, and the accumulation happens race-free in VMEM scratch
across a *sequential* ("arbitrary") grid dimension:

* pass 1 (`_dkv_kernel`): grid (B, Hq, kv_block, q_block) — dK/dV accumulate in
  scratch over the q_block dim (exactly the paper's per-thread-block dK/dV
  accumulation), written once on the last q iteration.
* pass 2 (`_dq_kernel`): grid (B, Hq, q_block, kv_block) — dQ accumulates over
  the kv_block dim, replacing the atomic adds.

Both passes **recompute the forward** from Q/K (the paper's memory-saving
choice) using the stored LSE — ``p = exp(s·scale − lse)`` — so S/P never exist
in HBM.  ``delta = rowsum(dO ∘ O)`` (the paper's *dPsum*) is precomputed once.
Dropout masks are regenerated from coordinates, bit-identical to the forward.

Both passes take flash_fwd's block schedule (kernels/schedule.py): tiles
chosen from the shape, and the sequential axis clamped into each row's needed
range — the kv blocks of a q block in pass 2, the q blocks of a kv block in
pass 1 (whose Q, dO, lse, delta and q-segment index maps clamp) — so a step
outside the range copies nothing and computes nothing.  Segment-packed
(varlen) batches mirror flash_fwd.py: per-token ``segment_ids`` stream in as
VMEM blocks, per-block min/max arrive via scalar-prefetch, and the ``pl.when``
early exits also skip (q-block, kv-block) pairs whose segment ranges cannot
intersect.  Negative ids mark padding (attends nothing, gets zero
gradient).  The ids, ``lse`` and delta use flash_fwd's tile-legal layouts
(its module docstring): ``lse`` and delta rows are broadcast across lanes.
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
from repro.kernels.common import LANES, SUBLANES, mosaic_kwargs


def _recompute_p(q, k, lse, *, scale, causal, window, q_start, kv_start,
                 block_q, block_kv, skv_real, acc_dtype,
                 dropout_rate, dropout_seed, b, h, q_seg=None, kv_seg=None):
    """Recompute probs p [bq, bkv] (f32) + dropout keep mask from stored LSE."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=acc_dtype)
    s = s.astype(jnp.float32) * scale
    qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    kp = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    allowed = None
    if causal:
        allowed = kp <= qp
    if window is not None:
        w_ok = kp > qp - window
        allowed = w_ok if allowed is None else (allowed & w_ok)
    pad_ok = kp < skv_real  # pad mask is cheap; always applied
    allowed = pad_ok if allowed is None else (allowed & pad_ok)
    if q_seg is not None:                  # q_seg [bq, 1], kv_seg [1, bkv]
        seg_ok = (q_seg == kv_seg) & (q_seg >= 0)
        allowed = allowed & seg_ok
    if allowed is not None:
        s = jnp.where(allowed, s, NEG_INF)
    # fully-masked rows store lse == NEG_INF; exp(s - lse) would be exp(0) = 1
    # there — substitute 0 so the recomputed probs are 0 (zero gradients).
    lse_safe = jnp.where(lse == NEG_INF, 0.0, lse)          # [bq, 1]
    # sparklint: disable=no-inline-softmax-fold -- not a fold: backward recompute of P from the stored LSE (guard is lse_safe above)
    p = jnp.exp(s - lse_safe)              # normalised probs, rows with lse
    keep = None
    if dropout_rate > 0.0:
        keep = rng.dropout_keep_mask(dropout_rate, dropout_seed, b, h, qp, kp)
    return p, keep


def _seg_unpack(refs, segments: bool):
    """Split the flat Pallas ref list into named groups for both bwd kernels.

    Layout: [seed, lo, hi, (4 segment bounds)] + [q, k, v, do, lse, delta,
    (qseg, kseg)] + n outputs + scratch.  ``lo``/``hi`` serve the index maps
    alone.  Returns (seed, bounds, tensors, qseg, kseg, outs+scratch).
    """
    if segments:
        seed_ref, bounds, refs = refs[0], refs[3:7], refs[7:]
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref = \
            refs[:8]
        rest = refs[8:]
    else:
        seed_ref, refs = refs[0], refs[3:]
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
        rest = refs[6:]
        bounds = qseg_ref = kseg_ref = None
    return (seed_ref, bounds, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref),
            qseg_ref, kseg_ref, rest)


def _dkv_kernel(*refs, scale, causal, window, dropout_rate,
                block_q, block_kv, sq_real, skv_real, acc_dtype, segments):
    (seed_ref, bounds, tensors, qseg_ref, kseg_ref, rest) = \
        _seg_unpack(refs, segments)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = tensors
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    b, h, ik, iq = (pl.program_id(i) for i in range(4))
    nq = pl.num_programs(3)
    q_offset = skv_real - sq_real
    q_start = iq * block_q + q_offset
    kv_start = ik * block_kv

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    needed = schedule.kernel_needed(
        b, iq, ik, bounds, block_q=block_q, block_kv=block_kv,
        sq_real=sq_real, skv_real=skv_real, causal=causal, window=window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]          # [bq, D]
        k = k_ref[0, 0]          # [bkv, D]
        v = v_ref[0, 0]
        do = do_ref[0, 0]        # [bq, D]
        lse = lse_ref[0, 0][:, :1]      # [bq, 1] f32 (lane-dense rows)
        delta = delta_ref[0, 0][:, :1]  # [bq, 1] f32

        p, keep = _recompute_p(
            q, k, lse, scale=scale, causal=causal, window=window,
            q_start=q_start, kv_start=kv_start, block_q=block_q,
            block_kv=block_kv, skv_real=skv_real, acc_dtype=acc_dtype,
            dropout_rate=dropout_rate, dropout_seed=seed_ref[0], b=b, h=h,
            q_seg=None if qseg_ref is None else qseg_ref[0][:, :1],
            kv_seg=None if kseg_ref is None else kseg_ref[0][:1, :])

        p_kept = p if keep is None else jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        # dV += P̃ᵀ · dO
        dv_acc[...] += jax.lax.dot_general(
            p_kept.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=acc_dtype).astype(jnp.float32)
        # dP = dO · Vᵀ  (masked by the same dropout keep-mask)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=acc_dtype).astype(jnp.float32)
        if keep is not None:
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        # dS = P ∘ (dP − delta) · scale   (delta = paper's dPsum)
        ds = p * (dp - delta) * scale
        # dK += dSᵀ · Q
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=acc_dtype).astype(jnp.float32)

    @pl.when(iq == nq - 1)
    def _write():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(*refs, scale, causal, window, dropout_rate,
               block_q, block_kv, sq_real, skv_real, acc_dtype, segments):
    (seed_ref, bounds, tensors, qseg_ref, kseg_ref, rest) = \
        _seg_unpack(refs, segments)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = tensors
    dq_ref, dq_acc = rest
    b, h, iq, ik = (pl.program_id(i) for i in range(4))
    nk = pl.num_programs(3)
    q_offset = skv_real - sq_real
    q_start = iq * block_q + q_offset
    kv_start = ik * block_kv

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    needed = schedule.kernel_needed(
        b, iq, ik, bounds, block_q=block_q, block_kv=block_kv,
        sq_real=sq_real, skv_real=skv_real, causal=causal, window=window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]

        p, keep = _recompute_p(
            q, k, lse, scale=scale, causal=causal, window=window,
            q_start=q_start, kv_start=kv_start, block_q=block_q,
            block_kv=block_kv, skv_real=skv_real, acc_dtype=acc_dtype,
            dropout_rate=dropout_rate, dropout_seed=seed_ref[0], b=b, h=h,
            q_seg=None if qseg_ref is None else qseg_ref[0][:, :1],
            kv_seg=None if kseg_ref is None else kseg_ref[0][:1, :])

        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=acc_dtype).astype(jnp.float32)
        if keep is not None:
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = p * (dp - delta) * scale
        # dQ += dS · K   — VMEM-scratch accumulation replaces the paper's atomics
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype).astype(jnp.float32)

    @pl.when(ik == nk - 1)
    def _write():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def flash_bwd(q, k, v, o, lse, do, *, causal: bool = False,
              window: Optional[int] = None, scale: Optional[float] = None,
              dropout_rate: float = 0.0, dropout_seed: int = 0,
              segment_ids=None, acc_dtype=jnp.float32,
              block_q: Optional[int] = None, block_kv: Optional[int] = None,
              interpret: bool = False):
    """Returns (dq, dk, dv) with the shapes/dtypes of q, k, v.

    block_q / block_kv: tile edges; None picks them from the shape
    (``schedule.tile``).
    """
    b, hq, sq_real, d = q.shape
    _, hkv, skv_real, _ = k.shape
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale

    # delta = rowsum(dO ∘ O) — the paper's dPsum, precomputed once (f32).
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    row_bytes = d * jnp.dtype(q.dtype).itemsize
    block_q = schedule.tile(sq_real, block_q, row_bytes)
    block_kv = schedule.tile(skv_real, block_kv, row_bytes)
    sq = pl.cdiv(sq_real, block_q) * block_q
    skv = pl.cdiv(skv_real, block_kv) * block_kv
    if sq != sq_real:
        pad_q = ((0, 0), (0, 0), (0, sq - sq_real), (0, 0))
        q = jnp.pad(q, pad_q)
        do = jnp.pad(do, pad_q)
        # padded rows: lse=+inf would give p=0; use large positive to zero probs
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, sq - sq_real)),
                      constant_values=-NEG_INF)
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, sq - sq_real)))
    if skv != skv_real:
        pad_kv = ((0, 0), (0, 0), (0, skv - skv_real), (0, 0))
        k = jnp.pad(k, pad_kv)
        v = jnp.pad(v, pad_kv)

    nq, nk = sq // block_q, skv // block_kv
    # per-row values travel lane-dense (flash_fwd module docstring)
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (LANES,))
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))
    segments = segment_ids is not None
    mask = dict(block_q=block_q, block_kv=block_kv, sq_real=sq_real,
                skv_real=skv_real, causal=causal, window=window)
    common = dict(scale=scale, dropout_rate=dropout_rate, acc_dtype=acc_dtype,
                  segments=segments, **mask)

    kwargs = mosaic_kwargs(
        interpret, ("parallel", "parallel", "parallel", "arbitrary"))

    seed = jnp.atleast_1d(jnp.asarray(dropout_seed, jnp.int32))
    seg_inputs = bounds = ()
    if segments:
        q_seg, kv_seg, bounds = schedule.pad_segments(
            segment_ids, b, sq_real, skv_real, sq, skv, block_q, block_kv)
        seg_inputs = (q_seg, kv_seg)
    needed = schedule.needed_blocks(b, nq, nk, bounds=bounds or None, **mask)

    # Both passes index their grid (b, h, i, j) with j sequential; the blocks
    # of j's sequence are clamped into row i's needed range [lo, hi], so a
    # step outside it fetches nothing new (flash_fwd does the same).
    seq = schedule.clamped

    # ---- pass 1: dK, dV (per q-head; GQA groups reduced below) ----
    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda b_, h, i, j, *r: (b_, h, seq(b_, i, j, *r), 0))
    kv_spec = pl.BlockSpec((1, 1, block_kv, d),
                           lambda b_, h, i, j, *_: (b_, h // group, i, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, LANES),
                            lambda b_, h, i, j, *r: (b_, h, seq(b_, i, j, *r), 0))
    in_specs1 = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    if segments:
        in_specs1 += [
            pl.BlockSpec((1, block_q, LANES),
                         lambda b_, h, i, j, *r: (b_, seq(b_, i, j, *r), 0)),
            pl.BlockSpec((1, SUBLANES, block_kv),
                         lambda b_, h, i, j, *_: (b_, 0, i)),
        ]
    prefetch = (seed, *schedule.block_range(needed, axis=1), *bounds)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, hq, nk, nq),
            in_specs=in_specs1,
            out_specs=[
                pl.BlockSpec((1, 1, block_kv, d),
                             lambda b_, h, i, j, *_: (b_, h, i, 0)),
                pl.BlockSpec((1, 1, block_kv, d),
                             lambda b_, h, i, j, *_: (b_, h, i, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                            pltpu.VMEM((block_kv, d), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, hq, skv, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
        **kwargs,
    )(*prefetch, q, k, v, do, lse, delta, *seg_inputs)

    # ---- pass 2: dQ ----
    q_spec2 = pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j, *_: (b_, h, i, 0))
    kv_spec2 = pl.BlockSpec(
        (1, 1, block_kv, d),
        lambda b_, h, i, j, *r: (b_, h // group, seq(b_, i, j, *r), 0))
    row_spec2 = pl.BlockSpec((1, 1, block_q, LANES),
                             lambda b_, h, i, j, *_: (b_, h, i, 0))
    in_specs2 = [q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2]
    if segments:
        in_specs2 += [
            pl.BlockSpec((1, block_q, LANES),
                         lambda b_, h, i, j, *_: (b_, i, 0)),
            pl.BlockSpec((1, SUBLANES, block_kv),
                         lambda b_, h, i, j, *r: (b_, 0, seq(b_, i, j, *r))),
        ]
    prefetch = (seed, *schedule.block_range(needed, axis=2), *bounds)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, hq, nq, nk),
            in_specs=in_specs2,
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda b_, h, i, j, *_: (b_, h, i, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
        **kwargs,
    )(*prefetch, q, k, v, do, lse, delta, *seg_inputs)

    if sq != sq_real:
        dq = dq[:, :, :sq_real]
    if skv != skv_real:
        dk = dk[:, :, :skv_real]
        dv = dv[:, :, :skv_real]
    if group > 1:  # GQA: reduce the per-q-head dK/dV over each group
        dk = dk.reshape(b, hkv, group, skv_real, d).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(b, hkv, group, skv_real, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv
