"""Public jit-friendly attention ops wiring the Pallas kernels into autodiff.

``mha`` is the trainable fused attention: forward = flash_fwd kernel, backward
= flash_bwd dual-pass kernels (with forward recompute), glued with
``jax.custom_vjp`` exactly the way the paper glues its CUDA kernels into
PyTorch autograd via pybind11.

``AttnConfig`` carries every static option (hashable → usable as a
nondiff argnum). The dropout seed is a *traced* scalar so a jitted train step
can use a fresh seed every step without recompilation.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_bwd import flash_bwd
from repro.kernels.flash_fwd import flash_fwd
from repro.kernels.decode import (flash_decode, flash_paged_decode,
                                  flash_paged_decode_partials)
from repro.kernels import ref


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    """Static attention options (hashable → usable as a jit nondiff argnum)."""
    causal: bool = False
    window: Optional[int] = None
    scale: Optional[float] = None
    dropout_rate: float = 0.0
    acc_dtype: Any = jnp.float32       # bf16-ACC / f32-ACC (paper §3.1)
    bwd_acc_dtype: Any = jnp.float32   # paper uses fp16-ACC for backward
    block_q: Optional[int] = None      # tile edges; None: chosen from the
    block_kv: Optional[int] = None     # shape (kernels/schedule.py)
    interpret: bool = False


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _mha(q, k, v, seed, segment_ids, cfg: AttnConfig):
    o, _ = flash_fwd(q, k, v, causal=cfg.causal, window=cfg.window,
                     scale=cfg.scale, dropout_rate=cfg.dropout_rate,
                     dropout_seed=seed, segment_ids=segment_ids,
                     acc_dtype=cfg.acc_dtype,
                     block_q=cfg.block_q, block_kv=cfg.block_kv,
                     interpret=cfg.interpret)
    return o


def _mha_fwd(q, k, v, seed, segment_ids, cfg: AttnConfig):
    o, lse = flash_fwd(q, k, v, causal=cfg.causal, window=cfg.window,
                       scale=cfg.scale, dropout_rate=cfg.dropout_rate,
                       dropout_seed=seed, segment_ids=segment_ids,
                       acc_dtype=cfg.acc_dtype,
                       block_q=cfg.block_q, block_kv=cfg.block_kv,
                       interpret=cfg.interpret)
    # Residuals: q,k,v + (o, lse) — S/P are recomputed in the backward kernels,
    # the paper's memory-saving choice (§3.3).
    return o, (q, k, v, o, lse, seed, segment_ids)


def _mha_bwd(cfg: AttnConfig, res, do):
    q, k, v, o, lse, seed, segment_ids = res
    dq, dk, dv = flash_bwd(q, k, v, o, lse, do, causal=cfg.causal,
                           window=cfg.window, scale=cfg.scale,
                           dropout_rate=cfg.dropout_rate, dropout_seed=seed,
                           segment_ids=segment_ids,
                           acc_dtype=cfg.bwd_acc_dtype,
                           block_q=cfg.block_q, block_kv=cfg.block_kv,
                           interpret=cfg.interpret)
    return dq, dk, dv, None, None


_mha.defvjp(_mha_fwd, _mha_bwd)


def mha(q, k, v, *, seed=0, segment_ids=None,
        config: AttnConfig = AttnConfig()):
    """Fused multi-head attention, differentiable.

    q: [B, Hq, Sq, D], k/v: [B, Hkv, Skv, D] → o: [B, Hq, Sq, D].
    segment_ids: optional [B, Skv] int32 per-token segment ids (packed/varlen
    batches); attention never crosses a segment boundary, negative ids mark
    padding. Carried as a traced residual (not in AttnConfig, which must stay
    hashable for the nondiff argnum) so a jitted train step can feed a fresh
    packing layout every step without recompilation.
    """
    seed = jnp.asarray(seed, jnp.int32)
    return _mha(q, k, v, seed, segment_ids, config)


def mha_reference(q, k, v, *, seed=0, segment_ids=None,
                  config: AttnConfig = AttnConfig()):
    """The unfused oracle with identical semantics (paper's PyTorch baseline)."""
    return ref.naive_mha(q, k, v, causal=config.causal, window=config.window,
                         scale=config.scale, dropout_rate=config.dropout_rate,
                         dropout_seed=seed, segment_ids=segment_ids,
                         acc_dtype=jnp.float32)


def mha_xla(q, k, v, *, seed=0, segment_ids=None,
            config: AttnConfig = AttnConfig(),
            chunk: int = 1024, unroll: bool = False):
    """The fused algorithm in plain XLA ops (dry-run / CPU-runnable path)."""
    return ref.online_mha(q, k, v, causal=config.causal, window=config.window,
                          scale=config.scale, dropout_rate=config.dropout_rate,
                          dropout_seed=seed, segment_ids=segment_ids,
                          acc_dtype=jnp.float32, chunk=chunk,
                          unroll=unroll)


def decode(q, k, v, *, kv_len=None, window=None, scale=None,
           block_kv: int = 512, num_splits: int = 1, interpret: bool = False):
    """Single-token flash-decode. q: [B, Hq, D], k/v: [B, Hkv, S, D].

    ``num_splits > 1`` partitions the KV axis over parallel grid cells whose
    partial states merge in f32 (split-KV; see kernels/decode.py and
    perf/autotune.py for the launch-parameter choice).
    """
    return flash_decode(q, k, v, kv_len=kv_len, window=window, scale=scale,
                        block_kv=block_kv, num_splits=num_splits,
                        interpret=interpret)


def paged_decode(q, k_pages, v_pages, block_tables, kv_len, *, window=None,
                 scale=None, num_splits: int = 1, interpret: bool = False):
    """Single-token flash-decode over a paged KV cache.

    q: [B, Hq, D]; k_pages/v_pages: [Hkv, num_pages, page_size, D];
    block_tables: [B, T] int32 (trash-page ids past each row's allocation);
    kv_len: [B] int32. ``num_splits`` splits the table width (see ``decode``).
    """
    return flash_paged_decode(q, k_pages, v_pages, block_tables, kv_len,
                              window=window, scale=scale,
                              num_splits=num_splits, interpret=interpret)


def paged_decode_partials(q, k_pages, v_pages, block_tables, kv_len, *,
                          block_valid=None, window=None, scale=None,
                          num_splits: int = 1, interpret: bool = False):
    """Paged flash-decode stopping at the (acc, m, l) online-softmax state.

    ``block_valid [B, T]`` (0/1) gates table entries — a shard of a
    page-sharded pool passes its locality mask so non-local entries (remapped
    to the local trash page) are skipped. States from different shards merge
    with ``online_softmax.merge`` and finalize once (distributed serving).
    ``num_splits`` splits shard-locally first; the returned triple is
    identical either way, so it composes with the cross-shard merge.
    """
    return flash_paged_decode_partials(q, k_pages, v_pages, block_tables,
                                       kv_len, block_valid=block_valid,
                                       window=window, scale=scale,
                                       num_splits=num_splits,
                                       interpret=interpret)


def gather_pages(pages, block_tables):
    """Materialise a paged pool as a contiguous cache (XLA / oracle path).

    pages [Hkv, num_pages, page_size, D], block_tables [B, T] →
    [B, Hkv, T*page_size, D].
    """
    hkv, _, ps, d = pages.shape
    b, t = block_tables.shape
    g = pages[:, block_tables]                    # [Hkv, B, T, ps, D]
    return g.transpose(1, 0, 2, 3, 4).reshape(b, hkv, t * ps, d)


def paged_decode_reference(q, k_pages, v_pages, block_tables, kv_len, *,
                           window=None, scale=None):
    """Oracle: gather the pages contiguously, then the contiguous oracle."""
    return decode_reference(q, gather_pages(k_pages, block_tables),
                            gather_pages(v_pages, block_tables),
                            kv_len=kv_len, window=window, scale=scale)


def decode_reference(q, k, v, *, kv_len=None, window=None, scale=None):
    """Oracle for decode (handles ragged kv_len row by row via masking)."""
    b, hq, d = q.shape
    skv = k.shape[2]
    if kv_len is None:
        return ref.naive_mha(q[:, :, None, :], k, v, causal=True,
                             window=window, scale=scale)[:, :, 0, :]
    outs = []
    for i in range(b):
        L = int(kv_len[i])
        outs.append(ref.naive_mha(q[i:i + 1, :, None, :], k[i:i + 1, :, :L],
                                  v[i:i + 1, :, :L], causal=True,
                                  window=window, scale=scale)[:, :, 0, :])
    return jnp.concatenate(outs, axis=0)
