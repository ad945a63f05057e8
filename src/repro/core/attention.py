"""SparkAttention public API — the paper's contribution as a composable module.

One entry point, three interchangeable execution paths:

* ``impl="pallas"``            — the fused Pallas TPU kernels (production path).
* ``impl="pallas_interpret"``  — same kernels, interpret mode (CPU validation).
* ``impl="xla"``               — the identical online-softmax algorithm as a
                                 chunked ``lax.scan`` in plain XLA; O(N) memory.
                                 Used by the CPU dry-run so lowered HLO matches
                                 the kernel algorithm's memory profile.
* ``impl="naive"``             — the unfused baseline (paper's PyTorch/cuBLAS
                                 comparison point). O(N²) memory.

All paths are numerically interchangeable (tests assert it) and differentiable.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ops import AttnConfig

IMPLS = ("pallas", "pallas_interpret", "xla", "naive")


def default_impl() -> str:
    """The impl for this platform: the Pallas kernels on a TPU, the same
    algorithm in plain XLA elsewhere (interpret mode is for tests only)."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def spark_attention(q, k, v, *, impl: str = "xla", seed=0,
                    causal: bool = False, window: Optional[int] = None,
                    scale: Optional[float] = None, dropout_rate: float = 0.0,
                    segment_ids=None,
                    acc_dtype=jnp.float32, bwd_acc_dtype=jnp.float32,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None,
                    xla_chunk: int = 1024, xla_unroll: bool = False):
    """Fused MHA. q [B,Hq,Sq,D], k/v [B,Hkv,Skv,D] → [B,Hq,Sq,D].

    segment_ids: optional [B, Skv] int32 per-token segment ids for packed
    (variable-length) batches — attention never crosses a segment boundary,
    negative ids mark padding tokens that attend to nothing. Supported by all
    four impls with identical semantics (tests assert interchangeability).
    block_q / block_kv: the Pallas kernels' tile edges; None (the default)
    lets the kernels choose them from the shape.
    """
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    cfg = AttnConfig(causal=causal, window=window, scale=scale,
                     dropout_rate=dropout_rate, acc_dtype=acc_dtype,
                     bwd_acc_dtype=bwd_acc_dtype, block_q=block_q,
                     block_kv=block_kv, interpret=(impl == "pallas_interpret"))
    if impl in ("pallas", "pallas_interpret"):
        return ops.mha(q, k, v, seed=seed, segment_ids=segment_ids, config=cfg)
    if impl == "xla":
        return ops.mha_xla(q, k, v, seed=seed, segment_ids=segment_ids,
                           config=cfg, chunk=xla_chunk, unroll=xla_unroll)
    return ops.mha_reference(q, k, v, seed=seed, segment_ids=segment_ids,
                             config=cfg)


def spark_decode(q, k, v, *, impl: str = "xla", kv_len=None,
                 window: Optional[int] = None, scale: Optional[float] = None,
                 block_kv: int = 512, num_splits: int = 1):
    """Single-token decode against a KV cache. q [B,Hq,D] → [B,Hq,D].

    num_splits > 1 runs the split-KV scheme on every impl: the KV axis is
    partitioned into that many slices whose un-normalised (acc, m, l) states
    merge in f32 (``online_softmax.merge_many``) — more parallel work at
    serving shapes for one tiny merge pass. ``perf/autotune.py`` picks the
    value; all impls stay numerically interchangeable (tests assert it).
    """
    if impl in ("pallas", "pallas_interpret"):
        return ops.decode(q, k, v, kv_len=kv_len, window=window, scale=scale,
                          block_kv=block_kv, num_splits=num_splits,
                          interpret=(impl == "pallas_interpret"))
    # XLA path: a single query row — the score vector is [B,H,S] (same order of
    # memory as one KV head slice), so the direct masked form is already I/O
    # optimal for decode. Splits mirror the kernel's partial-state algebra.
    if num_splits > 1:
        acc, m, l = _xla_split_decode_partials(q, k, v, kv_len=kv_len,
                                               window=window, scale=scale,
                                               num_splits=num_splits)
        from repro.core import online_softmax as osm
        o, _ = osm.finalize(osm.SoftmaxState(m=m, l=l, acc=acc),
                            out_dtype=q.dtype)
        return o
    return _xla_masked_decode(q, k, v, kv_len=kv_len, window=window, scale=scale)


def spark_paged_decode(q, k_pages, v_pages, block_tables, kv_len, *,
                       impl: str = "xla", window: Optional[int] = None,
                       scale: Optional[float] = None, num_splits: int = 1):
    """Single-token decode against a paged KV cache (serving subsystem).

    q [B,Hq,D]; k_pages/v_pages [Hkv,num_pages,page_size,D] global page pool;
    block_tables [B,T] int32 physical page per logical KV block (entries past a
    row's allocation must hold valid ids — the pool's trash page 0); kv_len [B].

    The Pallas path scalar-prefetches each row's block table and gathers its
    pages HBM→VMEM inside the kernel pipeline; the XLA path materialises the
    gather (jnp fancy-index) and reuses the contiguous masked decode — same
    numerics, used by the CPU dry-run and as the serving fallback.
    ``num_splits``: split-KV over the table width (see :func:`spark_decode`).
    """
    if impl in ("pallas", "pallas_interpret"):
        return ops.paged_decode(q, k_pages, v_pages, block_tables, kv_len,
                                window=window, scale=scale,
                                num_splits=num_splits,
                                interpret=(impl == "pallas_interpret"))
    return spark_decode(q, ops.gather_pages(k_pages, block_tables),
                        ops.gather_pages(v_pages, block_tables),
                        impl="xla", kv_len=kv_len, window=window, scale=scale,
                        num_splits=num_splits)


def spark_paged_decode_partials(q, k_pages, v_pages, block_tables, kv_len, *,
                                block_valid=None, impl: str = "xla",
                                window: Optional[int] = None,
                                scale: Optional[float] = None,
                                num_splits: int = 1):
    """Paged decode returning the un-finalized online-softmax state.

    The building block of *distributed* paged serving: each shard of a
    page-sharded pool calls this with its local pages, a block table remapped
    to local ids, and ``block_valid [B, T]`` marking the entries it owns
    (invalid entries point at the local trash page and contribute nothing).
    Returns f32 ``(acc [B,Hq,D], m [B,Hq], l [B,Hq])``; merge shards with the
    ``online_softmax`` algebra and finalize once (see distributed/paged.py).
    ``num_splits > 1`` computes the shard-local state as a merge of split-KV
    partials — identical output, so it composes with the cross-shard merge.
    """
    if impl in ("pallas", "pallas_interpret"):
        return ops.paged_decode_partials(
            q, k_pages, v_pages, block_tables, kv_len,
            block_valid=block_valid, window=window, scale=scale,
            num_splits=num_splits, interpret=(impl == "pallas_interpret"))
    ps = k_pages.shape[2]
    pos_valid = None
    if block_valid is not None:
        pos_valid = jnp.repeat(block_valid.astype(bool), ps, axis=1)
    if num_splits > 1:
        return _xla_split_decode_partials(
            q, ops.gather_pages(k_pages, block_tables),
            ops.gather_pages(v_pages, block_tables),
            kv_len=kv_len, window=window, scale=scale, pos_valid=pos_valid,
            num_splits=num_splits)
    return _xla_masked_decode_partials(
        q, ops.gather_pages(k_pages, block_tables),
        ops.gather_pages(v_pages, block_tables),
        kv_len=kv_len, window=window, scale=scale, pos_valid=pos_valid)


def _xla_masked_decode(q, k, v, *, kv_len=None, window=None, scale=None):
    from repro.core import online_softmax as osm
    acc, m, l = _xla_masked_decode_partials(q, k, v, kv_len=kv_len,
                                            window=window, scale=scale)
    o, _ = osm.finalize(osm.SoftmaxState(m=m, l=l, acc=acc),
                        out_dtype=q.dtype)
    return o


def _xla_masked_decode_partials(q, k, v, *, kv_len=None, window=None,
                                scale=None, pos_valid=None, kv_start=0):
    """Masked single-query decode, stopping at the un-normalised
    ``online_softmax`` state (acc, m, l) over the positions this caller is
    allowed to see (``pos_valid [B, Skv]`` gates shard-local ownership).
    ``kv_start`` offsets the slice's global positions — a split-KV chunk
    passes its slice of K/V plus its offset and gets the partial state over
    exactly its positions (``kv_len``/``window`` stay global).
    Fully-masked rows keep ``m == NEG_INF, l == 0, acc == 0`` so they merge
    and finalize to exact zeros, matching the kernels' convention.
    ``_xla_masked_decode`` is this plus ``online_softmax.finalize``."""
    from repro.core.online_softmax import NEG_INF
    from repro.kernels.ref import _expand_kv
    b, hq, d = q.shape
    skv = k.shape[2]
    scale = (d ** -0.5) if scale is None else scale
    kf = _expand_kv(k, hq)
    vf = _expand_kv(v, hq)
    s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                   kf.astype(jnp.float32)) * scale
    kp = kv_start + jnp.arange(skv)[None, None, :]
    if kv_len is None:
        kv_len = jnp.full((b,), kv_start + skv, jnp.int32)
    L = kv_len[:, None, None]
    allowed = kp < L
    if window is not None:
        allowed &= kp > (L - 1) - window
    if pos_valid is not None:
        allowed &= pos_valid[:, None, :]
    s = jnp.where(allowed, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(m == NEG_INF, 0.0, m)   # exp(NEG_INF - NEG_INF) == 1
    # sparklint: disable=no-inline-softmax-fold -- single-block partial state built in one shot with an explicit where(allowed); guard is m_safe above
    p = jnp.where(allowed, jnp.exp(s - m_safe[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhk,bhkd->bhd", p, vf.astype(jnp.float32))
    return acc, m, l


def _xla_split_decode_partials(q, k, v, *, kv_len=None, window=None,
                               scale=None, pos_valid=None, num_splits=2):
    """Split-KV decode in plain XLA: the kernel's scheme, mirrored.

    The KV axis is cut into ``num_splits`` contiguous slices; each slice's
    un-normalised state comes from :func:`_xla_masked_decode_partials` with
    its global ``kv_start`` offset, and the stacked states merge with the
    vectorized ``online_softmax.merge_many`` — the same algebra the Pallas
    split kernels use, so the dry-run's lowered HLO matches the kernel
    algorithm's parallelism structure.  Returns the merged (acc, m, l).
    """
    from repro.core import online_softmax as osm
    b = q.shape[0]
    skv = k.shape[2]
    num_splits = max(1, min(num_splits, skv))
    chunk = -(-skv // num_splits)
    if kv_len is None:
        kv_len = jnp.full((b,), skv, jnp.int32)
    parts = []
    for i in range(num_splits):
        lo, hi = i * chunk, min((i + 1) * chunk, skv)
        if lo >= hi:
            continue
        pv = None if pos_valid is None else pos_valid[:, lo:hi]
        acc, m, l = _xla_masked_decode_partials(
            q, k[:, :, lo:hi], v[:, :, lo:hi], kv_len=kv_len, window=window,
            scale=scale, pos_valid=pv, kv_start=lo)
        parts.append(osm.SoftmaxState(m=m, l=l, acc=acc))
    state = osm.merge_many(
        osm.SoftmaxState(m=jnp.stack([p.m for p in parts]),
                         l=jnp.stack([p.l for p in parts]),
                         acc=jnp.stack([p.acc for p in parts])), axis=0)
    return state.acc, state.m, state.l
