"""Model assembly: embeddings → scanned block stack → head, for all families.

Layer stacks are grouped into *superblocks* of ``len(block_pattern)`` layers and
scanned with ``jax.lax.scan`` over stacked params (compact HLO at 95 layers;
remat per superblock). Heterogeneous patterns (recurrentgemma's rec/rec/attn)
scan over the superblock period; trailing ``L % period`` layers run unscanned.

Public entry points:
  init_params(cfg, key, vocab_pad_to)      → (params, logical specs)
  forward(cfg, params, ctx, ...)           → (logits, caches, metrics)
  loss_fn / train metrics
  init_cache / prefill / decode_step       → KV-cache & recurrent-state serving
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import layers, mamba, moe, rglru
from repro.models.layers import Ctx

FRONTEND_DIM = 1024  # stub frontends hand us precomputed 1024-d patch/frame embeds


# ---------------------------------------------------------------------------
# per-block init/apply
# ---------------------------------------------------------------------------

def _init_block(key, cfg, kind: str, dtype):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": jnp.ones((cfg.d_model,), dtype)}
    s: Dict[str, Any] = {"norm1": ("embed",)}
    if kind == "attn":
        p["mixer"], s["mixer"] = layers.init_attention(ks[0], cfg, dtype)
    elif kind == "rec":
        p["mixer"], s["mixer"] = rglru.init_rglru(ks[0], cfg, dtype)
    elif kind == "ssm":
        p["mixer"], s["mixer"] = mamba.init_mamba(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    if kind != "ssm":  # mamba blocks are mixer-only (d_ff = 0)
        p["norm2"] = jnp.ones((cfg.d_model,), dtype)
        s["norm2"] = ("embed",)
        if cfg.moe is not None:
            p["mlp"], s["mlp"] = moe.init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"], s["mlp"] = layers.init_mlp(
                ks[1], cfg.d_model, cfg.d_ff, dtype,
                gated=(cfg.mlp_type == "gated_silu"))
    return p, s


def _apply_block(p, x, ctx: Ctx, cfg, kind: str, *, positions, cache,
                 layer_seed, segment_ids=None, paged=None):
    metrics = {}
    h = layers.rms_norm(x, p["norm1"])
    if kind == "attn":
        mixed, new_cache = layers.apply_attention(
            p["mixer"], h, ctx, cfg, positions=positions, cache=cache,
            layer_seed=layer_seed, segment_ids=segment_ids, paged=paged)
    elif kind == "rec":
        mixed, new_cache = rglru.apply_rglru(p["mixer"], h, ctx, cfg,
                                             cache=cache, positions=positions,
                                             paged=paged)
    else:
        mixed, new_cache = mamba.apply_mamba(p["mixer"], h, ctx, cfg,
                                             cache=cache, positions=positions,
                                             paged=paged)
    x = x + mixed
    if "mlp" in p:
        h = layers.rms_norm(x, p["norm2"])
        if cfg.moe is not None:
            out, metrics = moe.apply_moe(p["mlp"], h, ctx, cfg)
        else:
            out = layers.apply_mlp(p["mlp"], h, ctx,
                                   gated=(cfg.mlp_type == "gated_silu"))
        x = x + out
    return x, new_cache, metrics


# ---------------------------------------------------------------------------
# whole-model init
# ---------------------------------------------------------------------------

def padded_vocab(cfg, vocab_pad_to: int) -> int:
    return -(-cfg.vocab_size // vocab_pad_to) * vocab_pad_to


def init_params(cfg, key, *, vocab_pad_to: int = 1):
    period = len(cfg.block_pattern)
    n_super, rem = divmod(cfg.num_layers, period)
    vpad = padded_vocab(cfg, vocab_pad_to)
    keys = jax.random.split(key, 4 + cfg.num_layers)
    dtype = cfg.dtype

    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["embed"] = (jax.random.normal(keys[0], (vpad, cfg.d_model),
                                         jnp.float32) * 0.02).astype(dtype)
    specs["embed"] = ("vocab", "embed")
    if cfg.frontend is not None:
        params["frontend_proj"], specs["frontend_proj"] = layers.dense_init(
            keys[1], FRONTEND_DIM, cfg.d_model, dtype, None, "embed")
    params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
    specs["final_norm"] = ("embed",)
    params["lm_head"], specs["lm_head"] = layers.dense_init(
        keys[2], cfg.d_model, vpad, dtype, "embed", "vocab")

    # stacked superblocks: params["blocks"]["sub_j"][leaf][n_super, ...]
    def init_layer(i, k):
        kind = cfg.block_pattern[i % period]
        return _init_block(k, cfg, kind, dtype)

    if n_super > 0:
        subs_p, subs_s = {}, {}
        for j in range(period):
            layer_ids = jnp.arange(n_super) * period + j
            # one vmapped init per stacked sub-block: the same values as a
            # per-layer loop, but a jitted init holds one layer body whatever
            # the depth, so a sharded init of a deep model compiles quickly
            spec_box = {}

            def init_j(k, j=j):
                p, s = init_layer(j, k)
                spec_box["s"] = s
                return p
            subs_p[f"sub_{j}"] = jax.vmap(init_j)(keys[4 + layer_ids])
            subs_s[f"sub_{j}"] = jax.tree.map(
                lambda ax: ("layers",) + tuple(ax) if isinstance(ax, tuple)
                else ax, spec_box["s"],
                is_leaf=lambda x: isinstance(x, tuple))
        params["blocks"] = subs_p
        specs["blocks"] = subs_s
    tail_p, tail_s = {}, {}
    for r in range(rem):
        i = n_super * period + r
        tail_p[f"tail_{r}"], tail_s[f"tail_{r}"] = init_layer(i, keys[4 + i])
    if rem:
        params["tail"] = tail_p
        specs["tail"] = tail_s
    return params, specs


def abstract_params(cfg, *, vocab_pad_to: int = 1):
    """(ShapeDtypeStruct pytree, logical-spec pytree) with zero allocation."""
    box = {}

    def f(key):
        p, s = init_params(cfg, key, vocab_pad_to=vocab_pad_to)
        box["specs"] = s
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, box["specs"]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _block_kinds(cfg):
    period = len(cfg.block_pattern)
    n_super, rem = divmod(cfg.num_layers, period)
    return period, n_super, rem


def forward(cfg, params, ctx: Ctx, *, tokens=None, embeds=None, caches=None,
            positions=None, segment_ids=None, paged=None):
    """tokens [B,S] int32 OR embeds [B,S,FRONTEND_DIM]. Returns
    (logits [B,S,Vpad], new_caches, metrics).

    segment_ids [B,S]: packed-batch segment ids — attention blocks mask
    cross-segment pairs; pass per-segment ``positions`` alongside so RoPE
    restarts per packed sequence. Recurrent/SSM blocks carry state across
    the whole row regardless (packing is an attention-family feature).

    paged: paged-cache routing info forwarded to every attention block —
    {"dest": [B,S]} for packed prefill, {"block_tables": [B,T],
    "kv_len": [B]} for decode (see serving/paged_cache.py)."""
    period, n_super, rem = _block_kinds(cfg)
    if embeds is not None:
        x = embeds.astype(cfg.dtype) @ params["frontend_proj"]
    else:
        x = params["embed"][tokens]
    x = ctx.c(x, "batch", "seq", "embed")
    b, s = x.shape[:2]
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)

    metrics_acc = {"moe_aux": jnp.float32(0.0), "moe_dropped": jnp.float32(0.0)}
    n_moe = 0

    def apply_super(x, super_params, super_caches, super_idx):
        new_caches = {}
        mets = []
        for j in range(period):
            kind = cfg.block_pattern[j]
            cache_j = None if super_caches is None else super_caches[f"sub_{j}"]
            seed_off = super_idx * period + j
            x, nc, m = _apply_block(super_params[f"sub_{j}"], x, ctx, cfg, kind,
                                    positions=positions, cache=cache_j,
                                    layer_seed=seed_off * 1000003,
                                    segment_ids=segment_ids, paged=paged)
            new_caches[f"sub_{j}"] = nc
            if m:
                mets.append(m)
        msum = {}
        if mets:
            msum = {k: sum(m[k] for m in mets) for k in mets[0]}
        return x, new_caches, msum

    if n_super > 0:
        has_cache = caches is not None

        def scan_body(x, inp):
            idx, super_params, super_caches = inp
            x, nc, m = apply_super(x, super_params, super_caches, idx)
            if not m:
                m = {"moe_aux": jnp.float32(0.0),
                     "moe_dropped": jnp.float32(0.0)}
            out = (nc, m) if has_cache else (None, m)
            return x, out

        cache_stack = caches["blocks"] if has_cache else None
        if cfg.scan_layers:
            body = scan_body
            if cfg.remat:
                body = jax.checkpoint(scan_body,
                                      prevent_cse=False)  # remat/superblock
            idxs = jnp.arange(n_super)
            x, (new_cache_stack, ms) = jax.lax.scan(
                body, x, (idxs, params["blocks"], cache_stack))
        else:
            # unrolled stack (dry-run cost pass): identical math, flat HLO
            ncs, mss = [], []
            for i in range(n_super):
                sp = jax.tree.map(lambda a: a[i], params["blocks"])
                sc = (None if cache_stack is None
                      else jax.tree.map(lambda a: a[i], cache_stack))
                x, (nc, m) = scan_body(x, (jnp.int32(i), sp, sc))
                ncs.append(nc)
                mss.append(m)
            new_cache_stack = (jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)
                               if has_cache else None)
            ms = jax.tree.map(lambda *xs: jnp.stack(xs), *mss)
        if cfg.moe is not None:
            metrics_acc["moe_aux"] += jnp.sum(ms["moe_aux"])
            metrics_acc["moe_dropped"] += jnp.sum(ms["moe_dropped"])
            n_moe += n_super * period
    else:
        new_cache_stack = None

    new_tail = {}
    for r in range(rem):
        i = n_super * period + r
        kind = cfg.block_pattern[i % period]
        cache_r = None if caches is None else caches["tail"][f"tail_{r}"]
        x, nc, m = _apply_block(params["tail"][f"tail_{r}"], x, ctx, cfg, kind,
                                positions=positions, cache=cache_r,
                                layer_seed=i * 1000003,
                                segment_ids=segment_ids, paged=paged)
        new_tail[f"tail_{r}"] = nc
        if m:
            metrics_acc["moe_aux"] += m["moe_aux"]
            metrics_acc["moe_dropped"] += m["moe_dropped"]
            n_moe += 1

    x = layers.rms_norm(x, params["final_norm"])
    logits = x @ params["lm_head"]
    logits = ctx.c(logits, "batch", "seq", "vocab")

    if n_moe:
        metrics_acc["moe_dropped"] = metrics_acc["moe_dropped"] / n_moe
    new_caches = None
    if caches is not None:
        new_caches = {"blocks": new_cache_stack}
        if rem:
            new_caches["tail"] = new_tail
    return logits, new_caches, metrics_acc


# ---------------------------------------------------------------------------
# training loss
# ---------------------------------------------------------------------------

def loss_fn(cfg, params, batch, ctx: Ctx, *, aux_weight: float = 0.01):
    """batch: {'tokens' or 'embeds', 'labels'} (+ optional 'segment_ids',
    'positions' for packed batches). Next-token CE for causal LMs,
    per-position CE for encoders. Returns (loss, metrics)."""
    seg = batch.get("segment_ids")
    logits, _, metrics = forward(cfg, params, ctx,
                                 tokens=batch.get("tokens"),
                                 embeds=batch.get("embeds"),
                                 positions=batch.get("positions"),
                                 segment_ids=seg)
    labels = batch["labels"]
    weights = None
    if cfg.causal:
        logits = logits[:, :-1]
        labels = labels[:, 1:]
        if seg is not None:
            # a segment's last token must not be trained to predict the next
            # segment's first token (and padding predicts nothing)
            weights = ((seg[:, :-1] == seg[:, 1:]) &
                       (seg[:, 1:] >= 0)).astype(jnp.float32)
    elif seg is not None:
        weights = (seg >= 0).astype(jnp.float32)
    ce = layers.softmax_cross_entropy(logits, labels, cfg.vocab_size,
                                      weights=weights)
    loss = ce
    if cfg.moe is not None:
        loss = loss + aux_weight * metrics["moe_aux"]
    metrics = dict(metrics, ce=ce, loss=loss)
    return loss, metrics


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, dtype=None):
    dtype = dtype or cfg.dtype
    period, n_super, rem = _block_kinds(cfg)

    def one(kind):
        if kind == "attn":
            # sliding-window archs only ever need `window` cache slots
            eff = max_len if cfg.attn_window is None else min(
                max_len, cfg.attn_window)
            return layers.init_attn_cache(cfg, batch, eff, dtype)
        if kind == "rec":
            return rglru.init_rglru_cache(cfg, batch)
        return mamba.init_mamba_cache(cfg, batch)

    caches = {}
    if n_super > 0:
        caches["blocks"] = {
            f"sub_{j}": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_super,) + x.shape).copy(),
                one(cfg.block_pattern[j]))
            for j in range(period)}
    if rem:
        caches["tail"] = {f"tail_{r}": one(cfg.block_pattern[
            (n_super * period + r) % period]) for r in range(rem)}
    return caches


def prefill(cfg, params, ctx: Ctx, tokens=None, embeds=None, caches=None):
    """Run the full prompt, filling caches. Returns (last_logits, caches)."""
    logits, caches, _ = forward(cfg, params, ctx, tokens=tokens, embeds=embeds,
                                caches=caches)
    return logits[:, -1], caches


def decode_step(cfg, params, ctx: Ctx, token, caches, position):
    """One autoregressive step. token [B] int32 → (logits [B,Vpad], caches)."""
    ctx = layers.Ctx(**{**ctx.__dict__, "decode": True})
    b = token.shape[0]
    positions = jnp.broadcast_to(position, (b, 1)).astype(jnp.int32)
    logits, caches, _ = forward(cfg, params, ctx, tokens=token[:, None],
                                caches=caches, positions=positions)
    return logits[:, 0], caches


# ---------------------------------------------------------------------------
# paged serving: page-pool cache / packed prefill / block-table decode
# ---------------------------------------------------------------------------

def init_paged_cache(cfg, paged_cfg, dtype=None):
    """Serving cache per layer kind: attention blocks get page pools
    [Hkv, num_pages, page_size, D] (no batch dim — sequences share the pool
    via block tables); recurrent/SSM blocks get fixed per-slot state rows
    [max_batch + 1, ...] — O(1) per sequence, slot i backing decode slot i,
    plus one trailing *trash row* (index -1) that absorbs padding-token
    gathers/scatters exactly like the pool's trash page.  Host-side slot
    lifecycle lives in serving/state_cache.py."""
    dtype = dtype or cfg.dtype
    period, n_super, rem = _block_kinds(cfg)

    def one(kind):
        if kind == "attn":
            return layers.init_paged_attn_cache(cfg, paged_cfg, dtype)
        rows = paged_cfg.max_batch + 1        # + the trailing trash row
        if kind == "rec":
            return rglru.init_rglru_cache(cfg, rows)
        return mamba.init_mamba_cache(cfg, rows)

    caches = {}
    if n_super > 0:
        caches["blocks"] = {
            f"sub_{j}": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_super,) + x.shape).copy(),
                one(cfg.block_pattern[j]))
            for j in range(period)}
    if rem:
        caches["tail"] = {f"tail_{r}": one(cfg.block_pattern[
            (n_super * period + r) % period]) for r in range(rem)}
    return caches


def paged_prefill(cfg, params, ctx: Ctx, tokens, segment_ids, positions, dest,
                  caches, state_slots=None):
    """Segment-aware packed prefill: many prompts in one fused forward.

    tokens/segment_ids/positions [B, S] (prompts packed along S, -1 = pad,
    per-prompt positions restarting at 0); dest [B, S] flat page-pool token
    slots from BlockTables.prefill_dest. Returns (logits [B, S, Vpad], caches)
    — the engine reads each prompt's last-token row.

    state_slots [B, S] (hybrid SSM/recurrent archs): each token's decode
    slot, -1 for padding — recurrent blocks reset their scan at every span
    start and scatter span-end state into the slot's state row.  Classic
    prefill spans always start at position 0, so the per-segment positions
    double as the within-span offsets (state_local).
    """
    paged = {"dest": dest}
    if state_slots is not None:
        paged.update(state_slots=state_slots, state_local=positions)
    logits, caches, _ = forward(cfg, params, ctx, tokens=tokens, caches=caches,
                                positions=positions, segment_ids=segment_ids,
                                paged=paged)
    return logits, caches


def paged_chunk_prefill(cfg, params, ctx: Ctx, tokens, positions, dest,
                        token_tables, token_kv_len, caches,
                        state_slots=None, state_local=None):
    """Chunked / suffix packed prefill: prompt spans whose earlier tokens
    already live in pages (prefix-cache hits, earlier chunks of the same
    prompt).

    tokens/positions [B, S] with *global* per-token positions (RoPE must
    match what the prefix pages were written with); dest [B, S] flat
    page-pool token slots (BlockTables.span_dest, padding → trash);
    token_tables [B, S, T] each token's slot's block-table row;
    token_kv_len [B, S] = position + 1 for real tokens, 0 for padding.
    Each layer scatters the span's K/V into the pages first, then every
    token attends through its own block-table row — history and same-row
    predecessors alike — so no segment ids are needed (isolation comes from
    the tables).  Returns (logits [B, S, Vpad], caches); the engine reads a
    prompt's last-token row when its final chunk lands.

    state_slots/state_local [B, S] (hybrid SSM/recurrent archs): each
    token's decode slot (-1 pad) and offset within its span — a span whose
    global start (position - local) is past 0 resumes the slot's stored
    recurrent state (the previous chunk's span-end scatter).
    """
    paged = {"dest": dest, "token_tables": token_tables,
             "token_kv_len": token_kv_len}
    if state_slots is not None:
        paged.update(state_slots=state_slots, state_local=state_local)
    logits, caches, _ = forward(
        cfg, params, ctx, tokens=tokens, caches=caches, positions=positions,
        paged=paged)
    return logits, caches


def paged_verify_step(cfg, params, ctx: Ctx, tokens, positions, dest,
                      token_tables, token_kv_len, caches):
    """Speculative verify: score ``k + 1`` tokens per decode row in ONE
    forward call — the row's current last token plus its ``k`` drafted
    continuations — amortizing the per-step weight/KV HBM reads over up to
    ``k + 1`` emitted tokens.

    Inputs mirror :func:`paged_chunk_prefill` with ``[B = max_batch,
    W = k + 1]`` rows instead of packed prompt spans: tokens/positions
    ``[B, W]`` (global positions ``kv_len .. kv_len + k``), dest ``[B, W]``
    flat page-pool scatter slots (draft padding and masked rows → the trash
    page), token_tables ``[B, W, T]``, token_kv_len ``[B, W]`` =
    ``position + 1`` for live tokens and 0 for padding.  Each layer scatters
    all drafted K/V first, then every token attends through its own
    block-table row at its absolute position — drafted queries see the
    drafted keys before them, which is exactly the conditioning greedy
    acceptance needs (serving/drafter.py ``longest_accept``).

    The host accepts the longest draft prefix matching the per-position
    argmaxes and advances ``kv_len`` past it; rejected drafts' scatter
    writes are rolled back *logically* — they sit at positions ``>= kv_len``
    which every kernel read gates out, and the next step re-scatters those
    positions before ``kv_len`` ever covers them (docs/serving.md spells
    out the invariant).  Returns (logits [B, W, Vpad], caches).
    """
    return paged_chunk_prefill(cfg, params, ctx, tokens, positions, dest,
                               token_tables, token_kv_len, caches)


def paged_decode_step(cfg, params, ctx: Ctx, token, caches, block_tables,
                      kv_len):
    """One decode step over the paged cache. token [B] int32, block_tables
    [B, T], kv_len [B] (current lengths; the new token lands at position
    kv_len, and the engine increments host-side). → (logits [B,Vpad], caches).
    """
    ctx = layers.Ctx(**{**ctx.__dict__, "decode": True})
    positions = kv_len[:, None].astype(jnp.int32)
    logits, caches, _ = forward(
        cfg, params, ctx, tokens=token[:, None], caches=caches,
        positions=positions,
        paged={"block_tables": block_tables, "kv_len": kv_len})
    return logits[:, 0], caches
