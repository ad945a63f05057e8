"""Shared model building blocks (pure JAX, param pytrees + logical-axis specs).

Every ``init_*`` returns ``(params, specs)`` where ``specs`` mirrors the params
pytree with tuples of *logical axis names* per dim. The sharding-rule engine
(distributed/sharding.py) maps logical names → mesh axes per architecture.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.attention import (spark_attention, spark_decode,
                                  spark_paged_decode)
from repro.core.online_softmax import NEG_INF
from repro.distributed.sharding import (PER_SHARD_IMPLS, attention_per_shard,
                                        decode_per_shard)


# ---------------------------------------------------------------------------
# context threaded through every apply function
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Ctx:
    """Per-call context: mesh/sharding hooks + mode flags + dropout seed."""
    rules: Any = None                # distributed.ShardingRules (or None)
    impl: str = "xla"                # attention impl
    deterministic: bool = True       # disables dropout
    seed: Any = 0                    # traced dropout seed
    decode: bool = False             # single-token decode step
    xla_chunk: int = 1024
    xla_unroll: bool = False         # unroll attention chunk scans (cost pass)
    decode_write: str = "dus"        # KV write: "dus" | "onehot" (see below)
    block_q: int = 128
    block_kv: int = 128
    num_splits: int = 1              # split-KV decode grid cells per (B,Hkv)
                                     # row (kernels/decode.py; chosen by
                                     # perf/autotune.py when serving opts in)
    acc_dtype: Any = jnp.float32
    bwd_acc_dtype: Any = jnp.float32

    @property
    def mesh(self):
        """The rules' mesh, or None on one device.  With a paged cache,
        attention then routes its pool scatter/decode through the shard_map
        paths in distributed/paged.py."""
        return self.rules.mesh if self.rules is not None else None

    @property
    def per_shard(self) -> bool:
        """Run the attention kernels per shard (distributed/sharding.py):
        a Pallas impl on a mesh, which GSPMD cannot partition."""
        return self.rules is not None and self.impl in PER_SHARD_IMPLS

    def c(self, x, *axes):
        """Apply an activation sharding constraint if a mesh is attached."""
        if self.rules is None:
            return x
        return self.rules.constrain(x, axes)


# ---------------------------------------------------------------------------
# initialisers
# ---------------------------------------------------------------------------

def dense_init(key, d_in, d_out, dtype, in_axis="embed", out_axis="mlp",
               scale=None):
    scale = (d_in ** -0.5) if scale is None else scale
    w = (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)
    return w, (in_axis, out_axis)


def norm_init(dim, dtype):
    return jnp.ones((dim,), dtype), ("embed",)


# ---------------------------------------------------------------------------
# primitive ops
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(dt)


def rope(x, positions, *, base: float = 10000.0):
    """Rotary embedding. x: [B, S, H, D] (D even), positions: [B, S] or [S]."""
    b, s, h, d = x.shape
    half = d // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions.astype(jnp.float32)[:, :, None] * freqs[None, None, :]
    cos = jnp.cos(ang)[:, :, None, :]          # [B, S, 1, half]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def softmax_cross_entropy(logits, labels, vocab_size: int, weights=None):
    """Mean CE over positions. logits [B,S,V] (V may be padded), labels [B,S].

    weights: optional [B,S] per-position mask/weights — weighted mean over
    positions with weight > 0 (packed batches mask segment boundaries)."""
    logits = logits.astype(jnp.float32)
    if logits.shape[-1] > vocab_size:  # mask vocab padding
        neg = jnp.full((logits.shape[-1] - vocab_size,), NEG_INF, jnp.float32)
        logits = logits.at[..., vocab_size:].set(neg)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = lse - gold
    if weights is None:
        return jnp.mean(ce)
    w = weights.astype(jnp.float32)
    return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)


# ---------------------------------------------------------------------------
# MLP (dense FFN)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model, d_ff, dtype, gated: bool = True):
    k1, k2 = jax.random.split(key)
    width = 2 * d_ff if gated else d_ff
    wi, si = dense_init(k1, d_model, width, dtype, "embed", "mlp")
    wo, so = dense_init(k2, d_ff, d_model, dtype, "mlp", "embed")
    return {"wi": wi, "wo": wo}, {"wi": si, "wo": so}


def apply_mlp(p, x, ctx: Ctx, *, gated: bool = True):
    h = x @ p["wi"]
    h = ctx.c(h, "batch", "seq", "mlp")
    if gated:
        g, u = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(h)
    out = h @ p["wo"]
    return ctx.c(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Attention block (the paper's technique lives here)
# ---------------------------------------------------------------------------

def paged_decode_window(cfg) -> Optional[int]:
    """The sliding window the *paged* decode path masks with (None = full
    attention).

    Single source of truth shared by the kernel calls below and the serving
    engine's out-of-window page reclamation: the engine may free exactly the
    pages whose every position this mask excludes, so the two must agree or
    reclamation would free pages the kernel still reads.  (The contiguous
    decode path instead keeps a ``window``-slot ring buffer and needs no
    mask — see the decode branch in :func:`apply_attention`.)
    """
    return cfg.attn_window


def init_attention(key, cfg, dtype):
    """cfg: ArchConfig-like with num_heads/num_kv_heads/head_dim/d_model/qk_norm."""
    ks = jax.random.split(key, 4)
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p, s = {}, {}
    p["wq"], s["wq"] = dense_init(ks[0], d, hq * hd, dtype, "embed", "q_proj")
    p["wk"], s["wk"] = dense_init(ks[1], d, hkv * hd, dtype, "embed", "kv_proj")
    p["wv"], s["wv"] = dense_init(ks[2], d, hkv * hd, dtype, "embed", "kv_proj")
    p["wo"], s["wo"] = dense_init(ks[3], hq * hd, d, dtype, "q_proj", "embed")
    if cfg.qk_norm:
        p["q_norm"], s["q_norm"] = jnp.ones((hd,), dtype), ("head_dim",)
        p["k_norm"], s["k_norm"] = jnp.ones((hd,), dtype), ("head_dim",)
    return p, s


def apply_attention(p, x, ctx: Ctx, cfg, *, positions=None, cache=None,
                    layer_seed=0, segment_ids=None, paged=None):
    """x: [B, S, d]. Returns (out, new_cache).

    cache (decode/prefill): dict with k/v [B, Hkv, S_max, D] and index scalar,
    OR a *paged* cache dict with k_pages/v_pages [Hkv, num_pages, page_size, D]
    (a global page pool — see serving/paged_cache.py).
    segment_ids [B, S]: packed-batch segment ids — attention stays within a
    segment (pair with per-segment ``positions`` so RoPE restarts at each
    packed sequence). Training path, and packed *prefill* onto a paged cache.
    paged: serving-side routing for paged caches —
      prefill: {"dest": [B, S]} flat page-pool token slots per input token
      (padding → the trash page), precomputed by BlockTables.prefill_dest;
      decode: {"block_tables": [B, T], "kv_len": [B]};
      chunked/suffix prefill additionally carries {"token_tables": [B, S, T],
      "token_kv_len": [B, S]} — each token then attends through its own
      block-table row (history pages + same-row predecessors) instead of the
      in-row segment mask; positions are global per token.
    """
    b, s, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)

    q = (x @ p["wq"]).reshape(b, s, hq, hd)
    k = (x @ p["wk"]).reshape(b, s, hkv, hd)
    v = (x @ p["wv"]).reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = rope(q, positions)
    k = rope(k, positions)

    q = ctx.c(q.transpose(0, 2, 1, 3), "batch", "heads", "seq_full", "head_dim")
    k = ctx.c(k.transpose(0, 2, 1, 3), "batch", "kv_heads", "seq_full", "head_dim")
    v = ctx.c(v.transpose(0, 2, 1, 3), "batch", "kv_heads", "seq_full", "head_dim")

    new_cache = None
    if ctx.decode:
        # Append one token, then flash-decode over the cache. Sliding-window
        # archs use the cache as a RING buffer of `window` slots: RoPE bakes
        # absolute positions into K at write time and softmax is permutation-
        # invariant over keys, so slot order inside the ring is irrelevant and
        # no window mask is needed (every resident entry is in-window).
        assert s == 1 and cache is not None
        # the cache carries no segment structure, so a segment mask cannot be
        # honored here — packed prompts separate at prefill (paged path) and
        # decode as independent batch rows
        assert segment_ids is None, \
            "segment_ids apply to training and packed prefill; decode rows " \
            "are independent sequences"
        if "k_pages" in cache:
            # paged decode: append this token's K/V into its sequence's
            # current page (block_tables/kv_len name the slot), then
            # flash-decode with the block-table gather. Inactive slots point
            # at the trash page and carry kv_len == 0 — their writes and
            # logits are garbage by construction and ignored by the engine.
            assert paged is not None, "paged cache needs block_tables/kv_len"
            bt, kvl = paged["block_tables"], paged["kv_len"]
            if ctx.mesh is not None:
                # distributed pool (page dim sharded over the model axis):
                # per-shard local scatter + local attention, merged with the
                # online-softmax partial merge — see distributed/paged.py
                from repro.distributed.paged import paged_append_decode_sharded
                o, ck, cv = paged_append_decode_sharded(
                    q[:, :, 0, :], k[:, :, 0, :], v[:, :, 0, :],
                    cache["k_pages"], cache["v_pages"], bt, kvl,
                    mesh=ctx.mesh, impl=ctx.impl,
                    window=paged_decode_window(cfg),
                    num_splits=ctx.num_splits)
                o = o[:, :, None, :]
            else:
                ps = cache["k_pages"].shape[2]
                page = jnp.take_along_axis(bt, (kvl // ps)[:, None],
                                           axis=1)[:, 0]
                dest = page * ps + kvl % ps                   # [B] token slots
                ck = _scatter_pages(cache["k_pages"], dest,
                                    k[:, :, 0, :].transpose(1, 0, 2))
                cv = _scatter_pages(cache["v_pages"], dest,
                                    v[:, :, 0, :].transpose(1, 0, 2))
                # no ring buffer here — sliding windows mask inside the
                # kernel, and the engine frees fully-masked-out pages early
                # (their table entries revert to the trash page, which this
                # same window gate skips without reading)
                o = spark_paged_decode(q[:, :, 0, :], ck, cv, bt, kvl + 1,
                                       impl=ctx.impl,
                                       window=paged_decode_window(cfg),
                                       num_splits=ctx.num_splits
                                       )[:, :, None, :]
            new_cache = {"k_pages": ck, "v_pages": cv}
            o = ctx.c(o, "batch", "heads", "seq_full", "head_dim")
            out = o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd) @ p["wo"]
            return ctx.c(out, "batch", "seq", "embed"), new_cache
        idx = cache["index"]
        cap = cache["k"].shape[2]
        slot = idx % cap if cfg.attn_window is not None else idx
        if ctx.decode_write == "onehot":
            # Elementwise ring write: dynamic_update_slice at a traced index
            # on a sharded seq dim forces GSPMD into "involuntary full
            # rematerialization" (replicate + repartition the whole cache per
            # token — caught by the v0 dry-run). A one-hot select is
            # elementwise on the sharded dim → stays local on every shard.
            hot = (jnp.arange(cap, dtype=jnp.int32) == slot)[None, None, :, None]
            ck = jnp.where(hot, k.astype(cache["k"].dtype), cache["k"])
            cv = jnp.where(hot, v.astype(cache["v"].dtype), cache["v"])
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, slot, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, slot, 0))
        ck = ctx.c(ck, "batch", "kv_heads", "kv_cache_seq", "head_dim")
        cv = ctx.c(cv, "batch", "kv_heads", "kv_cache_seq", "head_dim")
        kv_len = jnp.full((b,), jnp.minimum(idx + 1, cap), jnp.int32)
        decode = (functools.partial(decode_per_shard, ctx.rules)
                  if ctx.per_shard else spark_decode)
        o = decode(q[:, :, 0, :], ck, cv, impl=ctx.impl, kv_len=kv_len,
                   window=None, block_kv=ctx.block_kv,
                   num_splits=ctx.num_splits)
        o = o[:, :, None, :]
        new_cache = {"k": ck, "v": cv, "index": idx + 1}
    else:
        if cache is not None and "k_pages" in cache:
            # segment-aware PACKED prefill: many prompts share one fused
            # forward row; the PR-1 segment mask keeps their attention
            # disjoint, and each token's K/V scatters into its own
            # sequence's pages via the precomputed dest slots (padding
            # tokens land in the trash page). One kernel launch fills every
            # admitted prompt's cache — no per-prompt padding traffic.
            assert paged is not None and "dest" in paged, \
                "packed prefill onto a paged cache needs dest token slots"
            dest = paged["dest"].reshape(-1)                  # [B*S]
            kv_vals = (k.transpose(1, 0, 2, 3).reshape(hkv, b * s, hd),
                       v.transpose(1, 0, 2, 3).reshape(hkv, b * s, hd))
            if ctx.mesh is not None:
                # sharded pool: each shard keeps the writes that land in its
                # pages; foreign tokens hit its local trash page
                from repro.distributed.paged import scatter_pages_sharded
                ck = scatter_pages_sharded(cache["k_pages"], dest, kv_vals[0],
                                           mesh=ctx.mesh)
                cv = scatter_pages_sharded(cache["v_pages"], dest, kv_vals[1],
                                           mesh=ctx.mesh)
            else:
                ck = _scatter_pages(cache["k_pages"], dest, kv_vals[0])
                cv = _scatter_pages(cache["v_pages"], dest, kv_vals[1])
            new_cache = {"k_pages": ck, "v_pages": cv}
            if "token_tables" in paged:
                # CHUNKED / suffix prefill: these tokens continue sequences
                # whose earlier tokens already live in pages (prefix-cache
                # hits, earlier chunks), so in-row attention is not enough.
                # The scatter above ran first, so each token can attend to
                # *everything* before it — history pages and same-row
                # predecessors alike — through one per-token block-table
                # read: token t becomes its own decode row with its slot's
                # table and kv_len = position + 1 (0 for padding → the
                # kv_len gate finalizes those rows to exact zeros).  No new
                # kernel: this is the split-KV paged decode with B·S rows.
                bt_tok = paged["token_tables"].reshape(b * s, -1)
                kvl_tok = paged["token_kv_len"].reshape(b * s)
                q_tok = q.transpose(0, 2, 1, 3).reshape(b * s, hq, hd)
                if ctx.mesh is not None:
                    from repro.distributed.paged import paged_decode_sharded
                    o_tok = paged_decode_sharded(
                        q_tok, ck, cv, bt_tok, kvl_tok, mesh=ctx.mesh,
                        impl=ctx.impl, window=paged_decode_window(cfg),
                        num_splits=ctx.num_splits)
                else:
                    o_tok = spark_paged_decode(
                        q_tok, ck, cv, bt_tok, kvl_tok, impl=ctx.impl,
                        window=paged_decode_window(cfg),
                        num_splits=ctx.num_splits)
                o = o_tok.reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
                o = ctx.c(o, "batch", "heads", "seq_full", "head_dim")
                out = o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd) @ p["wo"]
                return ctx.c(out, "batch", "seq", "embed"), new_cache
        elif cache is not None:  # contiguous prefill (position 0): fill it
            # this cache stores no segment structure, so a packed prefill
            # would silently decode across prompt boundaries later — packed
            # prefill requires the paged cache above
            assert segment_ids is None, \
                "packed prefill needs a paged cache (make_serve_steps paged=)"
            cap = cache["k"].shape[2]
            kc = k.astype(cache["k"].dtype)
            vc = v.astype(cache["v"].dtype)
            if s >= cap:  # windowed ring: keep the last `cap` tokens, by-slot
                shift = (s - cap) % cap
                kc = jnp.roll(kc[:, :, s - cap:], shift, axis=2)
                vc = jnp.roll(vc[:, :, s - cap:], shift, axis=2)
                ck, cv = kc, vc
            else:
                ck = jax.lax.dynamic_update_slice(cache["k"], kc, (0, 0, 0, 0))
                cv = jax.lax.dynamic_update_slice(cache["v"], vc, (0, 0, 0, 0))
            new_cache = {"k": ck, "v": cv, "index": cache["index"] + s}
        drop = 0.0 if ctx.deterministic else cfg.dropout_rate
        attend = (functools.partial(attention_per_shard, ctx.rules)
                  if ctx.per_shard else spark_attention)
        o = attend(q, k, v, impl=ctx.impl, seed=ctx.seed + layer_seed,
                   causal=cfg.causal, window=cfg.attn_window,
                   dropout_rate=drop, segment_ids=segment_ids,
                   acc_dtype=ctx.acc_dtype, bwd_acc_dtype=ctx.bwd_acc_dtype,
                   block_q=ctx.block_q, block_kv=ctx.block_kv,
                   xla_chunk=ctx.xla_chunk, xla_unroll=ctx.xla_unroll)

    o = ctx.c(o, "batch", "heads", "seq_full", "head_dim")
    out = o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd) @ p["wo"]
    return ctx.c(out, "batch", "seq", "embed"), new_cache


def init_attn_cache(cfg, batch, max_len, dtype):
    shape = (batch, cfg.num_kv_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "index": jnp.int32(0)}


def _scatter_pages(pages, dest, vals):
    """Write token rows into the page pool at flat token slots.

    pages [Hkv, num_pages, page_size, D]; dest [N] int32 flat slots
    (page * page_size + offset; duplicates only ever target the trash page);
    vals [Hkv, N, D].
    """
    hkv, n_pages, ps, d = pages.shape
    flat = pages.reshape(hkv, n_pages * ps, d)
    return flat.at[:, dest].set(vals.astype(pages.dtype)).reshape(pages.shape)


def init_paged_attn_cache(cfg, paged_cfg, dtype):
    """One attention layer's page pool (shared by all sequences; page 0 is
    the trash page — see serving/paged_cache.py)."""
    shape = (cfg.num_kv_heads, paged_cfg.num_pages, paged_cfg.page_size,
             cfg.head_dim)
    return {"k_pages": jnp.zeros(shape, dtype),
            "v_pages": jnp.zeros(shape, dtype)}
