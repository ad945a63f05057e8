"""Fused MHA-Forward Pallas TPU kernel (paper §3.2, adapted to MXU/VMEM).

One `pl.pallas_call` computes ``O = dropout(softmax(QKᵀ·scale))·V`` without ever
writing S or P to HBM — the paper's 3-reads + 1-write I/O profile.  The Volta
warp mechanics (m8n8k4 MMA, register layout transform between the two matmuls)
are replaced by their TPU-native equivalents:

* grid = (batch, q_head, q_block, kv_block); the kv_block dim is sequential
  ("arbitrary"), so the online-softmax state lives in VMEM scratch across
  iterations — the role the paper's registers/SRAM play on Volta.
* the S→P→(P·V) chain happens inside one kernel body; Mosaic owns the VREG
  relayout between the two `jnp.dot`s (the paper's warp-level layout transform).
* ``acc_dtype`` selects bf16-ACC / f32-ACC matmul accumulation
  (paper's FP16-ACC / FP32-ACC). Softmax state is always f32 (paper §3.2.1).
* causal / sliding-window blocks that are fully masked are skipped with
  `pl.when` (the paper's thread-block early exit).
* dropout masks are regenerated from element coordinates (kernels/rng.py), so
  the backward recompute sees identical masks with zero HBM mask traffic.

Segment-packed (varlen) batches: ``segment_ids [B, Skv]`` gives each kv token a
segment id; a token attends only within its own segment (negative ids mark
padding that attends to nothing and is attended by nothing).  The per-token ids
stream in as VMEM blocks aligned with the q/kv tiles, while per-block segment
min/max arrive via scalar-prefetch so the ``pl.when`` early exit also skips
blocks whose segment ranges cannot intersect — the same ragged-skip pattern as
``kv_len`` in kernels/decode.py.  The min/max interval test is exact-safe for
arbitrary ids (equal ids imply overlapping ranges) and tight for the packed
layout where ids are non-decreasing along the sequence.

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
from repro.kernels import rng
from repro.kernels.common import LANES, SUBLANES, mosaic_kwargs, online_fold


def _fwd_kernel(*refs, scale: float, causal: bool, window: Optional[int],
                dropout_rate: float,
                block_q: int, block_kv: int, sq: int, skv: int,
                sq_real: int, skv_real: int, acc_dtype, segments: bool):
    if segments:
        (seed_ref, qsmin_ref, qsmax_ref, ksmin_ref, ksmax_ref,  # scalar prefetch
         q_ref, k_ref, v_ref, qseg_ref, kseg_ref,               # inputs
         o_ref, lse_ref,                                        # outputs
         acc_ref, m_ref, l_ref) = refs                          # VMEM scratch
    else:
        (seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
         acc_ref, m_ref, l_ref) = refs
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

    # ---- block-level early exit (fully-masked blocks do no compute) ----
    needed = jnp.bool_(True)
    if causal:
        needed &= kv_start <= q_start + block_q - 1
    if window is not None:
        needed &= kv_start + block_kv - 1 > q_start - window
    if skv != skv_real:  # padded kv tail block may be entirely out of range
        needed &= kv_start < skv_real
    if segments:  # kv block's segment range must intersect the q block's
        needed &= (ksmin_ref[b, ik] <= qsmax_ref[b, iq]) & \
                  (ksmax_ref[b, ik] >= qsmin_ref[b, iq])

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
        if skv != skv_real:
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


def _pad_segments(segment_ids, b, sq_real, skv_real, sq, skv, nq, nk,
                  block_q, block_kv):
    """Pad per-token ids to block multiples and build per-block min/max.

    Returns (q_seg [B, sq, LANES], kv_seg [B, SUBLANES, skv], prefetch
    aggregates (qs_min, qs_max, ks_min, ks_max) each [B, n_blocks] int32).
    The ids are broadcast to tile-legal layouts (module docstring).  Padding
    uses -1: negative ids never match (`seg >= 0` in the kernels), and the
    min/max interval-overlap skip stays conservative-correct with them present.
    """
    kv_seg = jnp.asarray(segment_ids, jnp.int32)
    assert kv_seg.shape == (b, skv_real), (
        f"segment_ids must be [B, Skv] = {(b, skv_real)}, got {kv_seg.shape}")
    q_seg = kv_seg[:, skv_real - sq_real:]
    if skv != skv_real:
        kv_seg = jnp.pad(kv_seg, ((0, 0), (0, skv - skv_real)),
                         constant_values=-1)
    if sq != sq_real:
        q_seg = jnp.pad(q_seg, ((0, 0), (0, sq - sq_real)), constant_values=-1)
    qs = q_seg.reshape(b, nq, block_q)
    ks = kv_seg.reshape(b, nk, block_kv)
    aggs = (qs.min(-1), qs.max(-1), ks.min(-1), ks.max(-1))
    q_seg = jnp.broadcast_to(q_seg[:, :, None], (b, sq, LANES))
    kv_seg = jnp.broadcast_to(kv_seg[:, None, :], (b, SUBLANES, skv))
    return q_seg, kv_seg, aggs


def flash_fwd(q, k, v, *, causal: bool = False, window: Optional[int] = None,
              scale: Optional[float] = None, dropout_rate: float = 0.0,
              dropout_seed: int = 0, segment_ids=None, acc_dtype=jnp.float32,
              block_q: int = 128, block_kv: int = 128,
              interpret: bool = False):
    """Returns (o [B,Hq,Sq,D], lse [B,Hq,Sq] f32). Pads seq dims to block multiples.

    segment_ids: optional [B, Skv] int32 — per-token segment ids over the kv
    sequence (q tokens are its suffix). Attention is masked across segments;
    negative ids mark padding rows/keys that attend to nothing.
    """
    b, hq, sq_real, d = q.shape
    _, hkv, skv_real, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale

    block_q = min(block_q, max(sq_real, 8))
    block_kv = min(block_kv, max(skv_real, 8))
    sq = pl.cdiv(sq_real, block_q) * block_q
    skv = pl.cdiv(skv_real, block_kv) * block_kv
    if sq != sq_real:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq - sq_real), (0, 0)))
    if skv != skv_real:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv - skv_real), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv - skv_real), (0, 0)))

    nq, nk = sq // block_q, skv // block_kv
    segments = segment_ids is not None

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        dropout_rate=dropout_rate,
        block_q=block_q, block_kv=block_kv, sq=sq, skv=skv,
        sq_real=sq_real, skv_real=skv_real, acc_dtype=acc_dtype,
        segments=segments)

    kwargs = mosaic_kwargs(
        interpret, ("parallel", "parallel", "parallel", "arbitrary"))

    seed = jnp.atleast_1d(jnp.asarray(dropout_seed, jnp.int32))
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda b_, h, iq, ik, *_: (b_, h, iq, 0)),
        pl.BlockSpec((1, 1, block_kv, d),
                     lambda b_, h, iq, ik, *_: (b_, h // group, ik, 0)),
        pl.BlockSpec((1, 1, block_kv, d),
                     lambda b_, h, iq, ik, *_: (b_, h // group, ik, 0)),
    ]
    prefetch = (seed,)
    inputs = (q, k, v)
    if segments:
        q_seg, kv_seg, aggs = _pad_segments(
            segment_ids, b, sq_real, skv_real, sq, skv, nq, nk,
            block_q, block_kv)
        prefetch = prefetch + aggs
        inputs = inputs + (q_seg, kv_seg)
        in_specs += [
            pl.BlockSpec((1, block_q, LANES),
                         lambda b_, h, iq, ik, *_: (b_, iq, 0)),
            pl.BlockSpec((1, SUBLANES, block_kv),
                         lambda b_, h, iq, ik, *_: (b_, 0, ik)),
        ]

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
