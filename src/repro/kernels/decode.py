"""Flash-decode Pallas TPU kernel: one new query token vs. a long KV cache.

The paper's fused-MHA dataflow applied to the inference-decode shape
(``decode_32k`` / ``long_500k``): a single query row per (batch, kv-head)
streams the KV cache HBM→VMEM once, maintaining online-softmax state in VMEM
scratch.  This is purely memory-bound on TPU — the roofline term that matters
is HBM bytes = bytes(K) + bytes(V), which this kernel achieves exactly (the
naive path reads K, writes S, reads S, writes P, reads P and V: 3× more).

GQA: the ``G = Hq // Hkv`` query heads sharing one KV head are batched into the
MXU ``M`` dimension, so the two matmuls are [G,D]×[D,bkv] and [G,bkv]×[bkv,D] —
the TPU analogue of the paper packing multiple MMA computations per warp.

Ragged batches: ``kv_len [B]`` (scalar-prefetch) masks each row's valid cache
length, and fully-out-of-range KV blocks are skipped with ``pl.when``.

Split-KV (``num_splits > 1``): the sequential online-softmax loop over KV
blocks exposes only ``B·Hkv`` parallel work items — at serving shapes (small
continuous-batching batches, very long caches) that leaves most of the chip
idle.  The grid gains a *splits* axis: split ``s`` folds its contiguous slice
of KV blocks into its own un-normalised ``(acc, m, l)`` state (the same
partial-state trick the distributed path uses per shard), and a tiny
vectorized ``online_softmax.merge_many`` + ``finalize`` combines the splits —
``B·Hkv·num_splits`` parallel items for one extra O(B·Hq·D) merge pass.
``perf/autotune.py`` picks ``(num_splits, block_kv)`` from a cost model.

One kernel body (:func:`_decode_body`) serves every variant — contiguous,
paged, finalized or partial-state — parameterized by the scalar-prefetch
wrappers below; the fold itself is ``kernels.common.online_fold``.

Paged variant (:func:`flash_paged_decode`): the KV cache is a pool of
fixed-size pages ``[Hkv, num_pages, page_size, D]`` shared by all sequences;
each row's scalar-prefetched *block table* ``[B, T]`` names the physical page
backing its ``ik``-th logical KV block.  The page id feeds straight into the
K/V BlockSpec index map, so Mosaic's pipeline DMA gathers exactly the pages a
row owns HBM→VMEM — the kernel body is the same online-softmax loop with
``block_kv = page_size``.  Freed/unassigned table entries must point at a
valid page (the pool reserves page 0 as a trash page): the index map runs for
skipped blocks too, only the compute is gated by ``pl.when``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import online_softmax as osm
from repro.core.online_softmax import NEG_INF
from repro.kernels.common import LANES, mosaic_kwargs, online_fold

# grid axes of every decode kernel: (batch, kv_head, split, kv-block-in-split)
_DECODE_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")


def _decode_body(kv_len_ref, valid_ref, q_ref, k_ref, v_ref, rest, *,
                 scale: float, window: Optional[int], block_kv: int,
                 num_blocks: Optional[int], acc_dtype, finalize: bool):
    """The one decode loop body behind every kernel variant.

    Grid is always ``(B, Hkv, num_splits, blocks_per_split)``: program (b, h,
    s, j) folds global KV block ``ik = s·blocks_per_split + j`` into the
    (m, l, acc) scratch carried across the sequential ``j`` axis.  With
    ``finalize`` the last ``j`` writes the normalised output (valid only for
    ``num_splits == 1``); otherwise each split writes its raw state triple,
    merged by the caller (``online_softmax.merge_many``) — the same algebra
    the distributed path uses across shards.

    ``valid_ref [B, T]`` (optional) gates blocks the caller does not own
    (distributed pool shards); ``num_blocks`` gates trailing blocks past the
    real block count when the split layout over-covers (paged tables whose
    width does not divide by the split count).
    """
    *outs, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(3)
    nj = pl.num_programs(3)
    ik = pl.program_id(2) * nj + j                 # global KV block index
    kv_start = ik * block_kv
    kv_len = kv_len_ref[b]                         # valid cache length, this row
    q_pos = kv_len - 1                             # the query token's position

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    needed = kv_start < kv_len
    if num_blocks is not None:
        needed &= ik < num_blocks
    if valid_ref is not None:
        # clamp like the block-table index map: over-cover cells (ik >=
        # num_blocks) are compute-gated above but still evaluate this read
        needed &= valid_ref[b, jnp.minimum(ik, num_blocks - 1)] != 0
    if window is not None:
        needed &= kv_start + block_kv - 1 > q_pos - window

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]                            # [G, D]
        k = k_ref[0, 0]                            # [bkv, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=acc_dtype)
        s = s.astype(jnp.float32) * scale          # [G, bkv]
        kp = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        allowed = kp < kv_len
        if window is not None:
            allowed &= kp > q_pos - window
        s = jnp.where(allowed, s, NEG_INF)
        online_fold(s, v_ref[0, 0], acc_ref, m_ref, l_ref, acc_dtype=acc_dtype)

    @pl.when(j == nj - 1)
    def _write():
        if finalize:
            (o_ref,) = outs
            l = l_ref[:, 0]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        else:
            acc_out, m_out, l_out = outs
            acc_out[0, 0, 0] = acc_ref[...].astype(acc_out.dtype)
            m_out[0, 0, 0] = m_ref[...].astype(m_out.dtype)
            l_out[0, 0, 0] = l_ref[...].astype(l_out.dtype)


def _contig_kernel(kv_len_ref, q_ref, k_ref, v_ref, *rest, **kw):
    # contiguous cache: kv_len is the only scalar-prefetch operand
    _decode_body(kv_len_ref, None, q_ref, k_ref, v_ref, rest, **kw)


def _paged_kernel(kv_len_ref, bt_ref, q_ref, k_ref, v_ref, *rest, **kw):
    # the block table is consumed entirely by the K/V BlockSpec index maps;
    # inside the body the gathered page is indistinguishable from a contiguous
    # cache block, so the loop is shared with the contiguous kernel
    del bt_ref
    _decode_body(kv_len_ref, None, q_ref, k_ref, v_ref, rest, **kw)


def _paged_valid_kernel(kv_len_ref, bt_ref, valid_ref, q_ref, k_ref, v_ref,
                        *rest, **kw):
    # blocks with valid_ref[b, ik] == 0 are skipped entirely: the distributed
    # path marks non-local table entries invalid (they point at the local
    # trash page)
    del bt_ref
    _decode_body(kv_len_ref, valid_ref, q_ref, k_ref, v_ref, rest, **kw)


def _group_pad(q, b, hkv, group, d):
    """[B, Hq, D] → [B, Hkv, G_pad, D] with G padded up to the 8-row MXU tile."""
    qg = q.reshape(b, hkv, group, d)
    g_pad = max(8, group)
    if g_pad != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))
    return qg, g_pad


def _decode_out_shapes(b, hkv, ns, g_pad, d, out_dtype, finalize: bool):
    """(out_shape, out_specs) for the finalized / partial kernel variants.

    The index maps absorb trailing scalar-prefetch refs with ``*_``; partial
    outputs carry the splits axis so every (b, h, split) cell writes its own
    state block.
    """
    def _ix(b_, h, s_, j, *_):
        return (b_, h, 0, 0)

    def _ix_split(b_, h, s_, j, *_):
        return (b_, h, s_, 0, 0)

    if finalize:
        return (jax.ShapeDtypeStruct((b, hkv, g_pad, d), out_dtype),
                pl.BlockSpec((1, 1, g_pad, d), _ix))
    out_shape = [jax.ShapeDtypeStruct((b, hkv, ns, g_pad, d), jnp.float32),
                 jax.ShapeDtypeStruct((b, hkv, ns, g_pad, LANES), jnp.float32),
                 jax.ShapeDtypeStruct((b, hkv, ns, g_pad, LANES), jnp.float32)]
    out_specs = [pl.BlockSpec((1, 1, 1, g_pad, d), _ix_split),
                 pl.BlockSpec((1, 1, 1, g_pad, LANES), _ix_split),
                 pl.BlockSpec((1, 1, 1, g_pad, LANES), _ix_split)]
    return out_shape, out_specs


def _split_states(acc, m, l, group, b, hq):
    """Kernel partial outputs → a SoftmaxState stacked on the splits axis.

    acc [B,Hkv,ns,G_pad,D], m/l [B,Hkv,ns,G_pad,LANES] → state with
    m/l [B,ns,Hq] and acc [B,ns,Hq,D] (splits axis 1, ready for merge_many).
    """
    ns, d = acc.shape[2], acc.shape[-1]
    acc = acc[:, :, :, :group].transpose(0, 2, 1, 3, 4).reshape(b, ns, hq, d)
    m = m[:, :, :, :group, 0].transpose(0, 2, 1, 3).reshape(b, ns, hq)
    l = l[:, :, :, :group, 0].transpose(0, 2, 1, 3).reshape(b, ns, hq)
    return osm.SoftmaxState(m=m, l=l, acc=acc)


def flash_decode(q, k, v, *, kv_len=None, window: Optional[int] = None,
                 scale: Optional[float] = None, acc_dtype=jnp.float32,
                 block_kv: int = 512, num_splits: int = 1,
                 interpret: bool = False):
    """q: [B, Hq, D]; k/v: [B, Hkv, S, D]; kv_len: [B] int32 (default: full S).

    num_splits > 1 partitions the KV axis across that many parallel grid
    cells, each producing an un-normalised partial state, merged in f32 by
    ``online_softmax.merge_many`` (module docstring). Returns o: [B, Hq, D]
    in q.dtype.
    """
    b, hq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    if kv_len is None:
        kv_len = jnp.full((b,), skv, jnp.int32)

    # clamp the block to the cache length, but keep KV tiles 8-row aligned:
    # a short cache (skv < 8) must not produce a sub-8-row tile — pad instead
    block_kv = min(block_kv, max(skv, 8))
    block_kv = -(-block_kv // 8) * 8
    nk = pl.cdiv(skv, block_kv)
    num_splits = max(1, min(num_splits, nk))
    nj = pl.cdiv(nk, num_splits)                   # KV blocks per split
    skv_pad = nk * block_kv                        # remainder pad only —
    if skv_pad != skv:                             # split over-cover cells
        pad = ((0, 0), (0, 0), (0, skv_pad - skv), (0, 0))  # (ik >= nk) are
        k = jnp.pad(k, pad)                        # compute-gated + index-
        v = jnp.pad(v, pad)                        # clamped, no data needed

    qg, g_pad = _group_pad(q, b, hkv, group, d)
    finalize = num_splits == 1

    def _kv_ix(b_, h, s_, j, *_):
        return (b_, h, jnp.minimum(s_ * nj + j, nk - 1), 0)

    kernel = functools.partial(_contig_kernel, scale=scale, window=window,
                               block_kv=block_kv, num_blocks=nk,
                               acc_dtype=acc_dtype, finalize=finalize)
    out_shape, out_specs = _decode_out_shapes(
        b, hkv, num_splits, g_pad, d, q.dtype, finalize)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, num_splits, nj),
        in_specs=[
            pl.BlockSpec((1, 1, g_pad, d),
                         lambda b_, h, s_, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d), _kv_ix),
            pl.BlockSpec((1, 1, block_kv, d), _kv_ix),
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((g_pad, d), jnp.float32),
                        pltpu.VMEM((g_pad, LANES), jnp.float32),
                        pltpu.VMEM((g_pad, LANES), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="decode",
        **mosaic_kwargs(interpret, _DECODE_SEMANTICS),
    )(kv_len.astype(jnp.int32), qg, k, v)
    if finalize:
        return out[:, :, :group].reshape(b, hq, d)
    state = osm.merge_many(_split_states(*out, group, b, hq), axis=1)
    o, _ = osm.finalize(state, out_dtype=q.dtype)
    return o


def _paged_call(kernel_fn, prefetch, qg, k_pages, v_pages, *, name, b, hkv,
                ns, nj, t, g_pad, d, page_size, out_dtype, finalize, scale,
                window, acc_dtype, interpret):
    """Shared pallas_call launch for the paged variants (finalized/partial),
    named ``name`` in the compiled program and the profiler's trace.

    ``prefetch`` is the scalar-prefetch tuple starting with (kv_len,
    block_tables[, block_valid]); the K/V index maps read the table at the
    global block index ``s·nj + j`` (clamped — trailing cells past the table
    width are compute-gated by ``num_blocks``).
    """
    n_pre = len(prefetch)

    def _kv_ix(b_, h, s_, j, kvl, bt, *_):
        ik = jnp.minimum(s_ * nj + j, t - 1)
        return (h, bt[b_, ik], 0, 0)

    kernel = functools.partial(kernel_fn, scale=scale, window=window,
                               block_kv=page_size, num_blocks=t,
                               acc_dtype=acc_dtype, finalize=finalize)
    out_shape, out_specs = _decode_out_shapes(
        b, hkv, ns, g_pad, d, out_dtype, finalize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre,
        grid=(b, hkv, ns, nj),
        in_specs=[
            pl.BlockSpec((1, 1, g_pad, d),
                         lambda b_, h, s_, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d), _kv_ix),
            pl.BlockSpec((1, 1, page_size, d), _kv_ix),
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((g_pad, d), jnp.float32),
                        pltpu.VMEM((g_pad, LANES), jnp.float32),
                        pltpu.VMEM((g_pad, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name=name,
        **mosaic_kwargs(interpret, _DECODE_SEMANTICS),
    )(*prefetch, qg, k_pages, v_pages)


def flash_paged_decode(q, k_pages, v_pages, block_tables, kv_len, *,
                       window: Optional[int] = None,
                       scale: Optional[float] = None, acc_dtype=jnp.float32,
                       num_splits: int = 1, interpret: bool = False):
    """Flash-decode against a paged KV cache.

    q: [B, Hq, D]; k_pages/v_pages: [Hkv, num_pages, page_size, D] (global page
    pool); block_tables: [B, T] int32 physical page ids per logical KV block
    (entries past a row's allocation must still be valid ids — use the pool's
    trash page 0); kv_len: [B] int32 valid cache length per row. num_splits
    partitions the table width T across parallel grid cells (module
    docstring).

    Returns o: [B, Hq, D] in q.dtype.
    """
    b, hq, d = q.shape
    hkv, _, page_size, _ = k_pages.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    t = block_tables.shape[1]
    num_splits = max(1, min(num_splits, t))
    nj = pl.cdiv(t, num_splits)
    qg, g_pad = _group_pad(q, b, hkv, group, d)
    finalize = num_splits == 1

    prefetch = (kv_len.astype(jnp.int32), block_tables.astype(jnp.int32))
    out = _paged_call(_paged_kernel, prefetch, qg, k_pages, v_pages,
                      name="paged_decode", b=b, hkv=hkv, ns=num_splits,
                      nj=nj, t=t, g_pad=g_pad, d=d, page_size=page_size,
                      out_dtype=q.dtype,
                      finalize=finalize, scale=scale, window=window,
                      acc_dtype=acc_dtype, interpret=interpret)
    if finalize:
        return out[:, :, :group].reshape(b, hq, d)
    state = osm.merge_many(_split_states(*out, group, b, hq), axis=1)
    o, _ = osm.finalize(state, out_dtype=q.dtype)
    return o


def flash_paged_decode_partials(q, k_pages, v_pages, block_tables, kv_len, *,
                                block_valid=None, window: Optional[int] = None,
                                scale: Optional[float] = None,
                                acc_dtype=jnp.float32, num_splits: int = 1,
                                interpret: bool = False):
    """Paged flash-decode returning the un-finalized online-softmax state.

    Same arguments as :func:`flash_paged_decode` plus ``block_valid [B, T]``
    (int32/bool; 0 marks table entries this caller does not own — the
    distributed path passes the locality mask of its pool shard and remaps
    those entries to its local trash page).  Returns the f32 triple
    ``(acc [B,Hq,D], m [B,Hq], l [B,Hq])`` for ``online_softmax.merge`` /
    ``finalize`` — shards of a page-sharded pool each compute their local
    state, then a tiny all-reduce merges them (distributed paged serving).
    With ``num_splits > 1`` the shard-local splits are merged locally first
    (``merge_many``), composing with the cross-shard merge — the returned
    triple is identical either way.
    """
    b, hq, d = q.shape
    hkv, _, page_size, _ = k_pages.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    t = block_tables.shape[1]
    num_splits = max(1, min(num_splits, t))
    nj = pl.cdiv(t, num_splits)
    if block_valid is None:
        block_valid = jnp.ones((b, t), jnp.int32)
    qg, g_pad = _group_pad(q, b, hkv, group, d)

    prefetch = (kv_len.astype(jnp.int32), block_tables.astype(jnp.int32),
                block_valid.astype(jnp.int32))
    acc, m, l = _paged_call(_paged_valid_kernel, prefetch, qg, k_pages,
                            v_pages, name="paged_decode_partials", b=b,
                            hkv=hkv, ns=num_splits, nj=nj, t=t, g_pad=g_pad, d=d, page_size=page_size,
                            out_dtype=jnp.float32, finalize=False,
                            scale=scale, window=window, acc_dtype=acc_dtype,
                            interpret=interpret)
    state = osm.merge_many(_split_states(acc, m, l, group, b, hq), axis=1)
    return state.acc, state.m, state.l
