"""Segment-packed (varlen) attention: all impls vs. a per-segment oracle.

The contract: ``spark_attention(..., segment_ids=...)`` on a packed batch is
numerically identical (≤1e-3 max-abs) to running each segment through the
naive reference independently — for forward AND gradients, on every impl.
Negative segment ids are padding: zero output, zero gradient.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_qkv, max_err
from repro.core.attention import spark_attention
from repro.kernels.flash_fwd import flash_fwd
from repro.kernels.flash_bwd import flash_bwd
from repro.kernels.ref import naive_mha

IMPLS = ("naive", "xla", "pallas_interpret")


def _segments(lengths, total, pad=False):
    """Non-decreasing segment ids from a list of lengths; -1 pads the tail."""
    ids = np.full((total,), -1 if pad else 0, np.int32)
    t = 0
    for sid, L in enumerate(lengths):
        ids[t:t + L] = sid
        t += L
    if not pad:
        assert t == total, "lengths must fill the row unless pad=True"
    return ids


def _per_segment_oracle(q, k, v, seg, *, causal):
    """Loop over segments, run the naive kernel on each slice independently.
    Assumes sq == skv (full self-attention rows). Padding (-1) rows → 0."""
    out = np.zeros(np.asarray(q).shape, np.float32)
    b = q.shape[0]
    for i in range(b):
        ids = np.asarray(seg[i])
        for sid in np.unique(ids[ids >= 0]):
            idx = np.where(ids == sid)[0]
            o = naive_mha(q[i:i + 1, :, idx], k[i:i + 1, :, idx],
                          v[i:i + 1, :, idx], causal=causal)
            out[i][:, idx] = np.asarray(o[0])
    return out


CASES = [
    # b, hq, hkv, s, d, lengths (per-row packing layout), causal, bq, bkv
    (2, 4, 4, 128, 32, [50, 40, 38], True, 32, 32),
    (2, 4, 2, 128, 32, [50, 40, 38], True, 32, 32),     # GQA
    (1, 2, 2, 128, 64, [128], False, 64, 64),           # single segment ≡ dense
    (1, 2, 2, 100, 32, [33, 40, 27], True, 32, 32),     # non-block-multiple seq
    (1, 8, 1, 96, 32, [8, 88], True, 32, 32),           # MQA, tiny first seg
]
# the real kernel bodies (pallas_interpret) run the distinctive cases; the
# cheaper oracle impls sample two apiece. CASES[0] (plain MHA) only runs on
# naive/xla — the group-1 pallas path is already exercised by every other
# pallas test in this file.
CASE_MATRIX = ([("pallas_interpret", c) for c in CASES[1:]] +
               [(i, c) for i in ("naive", "xla") for c in (CASES[0], CASES[3])])


@pytest.mark.parametrize("impl,case", CASE_MATRIX,
                         ids=[f"{i}-{c[:5]}{c[5]}" for i, c in CASE_MATRIX])
def test_varlen_fwd_matches_per_segment_oracle(rng_key, impl, case):
    b, hq, hkv, s, d, lengths, causal, bq, bkv = case
    q, k, v, _ = make_qkv(rng_key, b, hq, hkv, s, s, d)
    seg = jnp.asarray(np.tile(_segments(lengths, s), (b, 1)))
    o = spark_attention(q, k, v, impl=impl, causal=causal, segment_ids=seg,
                        block_q=bq, block_kv=bkv, xla_chunk=bkv)
    o_ref = _per_segment_oracle(q, k, v, seg, causal=causal)
    assert max_err(o, o_ref) < 1e-3


_GREF_CACHE = {}


@pytest.mark.parametrize("impl", IMPLS)
def test_varlen_grads_match_per_segment_oracle(rng_key, impl):
    b, hq, hkv, s, d = 1, 4, 2, 64, 32
    q, k, v, do = make_qkv(rng_key, b, hq, hkv, s, s, d)
    seg = jnp.asarray(_segments([28, 21, 15], s))[None, :]

    def loss(impl_):
        def f(q, k, v):
            o = spark_attention(q, k, v, impl=impl_, causal=True,
                                segment_ids=seg, block_q=32, block_kv=32,
                                xla_chunk=32)
            return (o * do).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    # gradient oracle: per-segment naive attention, summed. Inputs are a pure
    # function of the session rng_key, so share it across the impl params.
    def f_ref(q, k, v):
        tot = 0.0
        ids = np.asarray(seg[0])
        for sid in np.unique(ids):
            idx = np.where(ids == sid)[0]
            o = naive_mha(q[:, :, idx], k[:, :, idx], v[:, :, idx], causal=True)
            tot = tot + (o * do[:, :, idx]).sum()
        return tot

    if "g_ref" not in _GREF_CACHE:
        _GREF_CACHE["g_ref"] = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_ref = _GREF_CACHE["g_ref"]
    g = loss(impl)
    for a, r in zip(g, g_ref):
        assert max_err(a, r) < 1e-3


@pytest.mark.parametrize("impl", IMPLS)
def test_varlen_padding_rows_zero_fwd_and_grad(rng_key, impl):
    """Negative segment ids = padding: o == 0 (the l==0 finalize path in
    flash_fwd) and exactly zero gradient flows through padded tokens."""
    b, h, s, d = 1, 2, 96, 32
    q, k, v, do = make_qkv(rng_key, b, h, h, s, s, d)
    seg = jnp.asarray(_segments([40, 24], s, pad=True))[None, :]  # 32-token pad

    def f(q, k, v):
        o = spark_attention(q, k, v, impl=impl, causal=True, segment_ids=seg,
                            block_q=32, block_kv=32, xla_chunk=32)
        return (o * do).sum(), o

    (_, o), g = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    o = np.asarray(o)
    assert not np.isnan(o).any()
    assert np.abs(o[:, :, 64:]).max() == 0.0          # padded q rows → zeros
    for gi in g:
        assert not bool(jnp.isnan(gi).any())
        assert float(jnp.abs(gi[:, :, 64:]).max()) == 0.0  # no grad into pad


def test_varlen_block_decomposition_invariance(rng_key):
    """Same packing, different block sizes → identical outputs (the segment
    block-skip must only skip provably-empty blocks)."""
    b, h, s, d = 1, 2, 128, 32
    q, k, v, _ = make_qkv(rng_key, b, h, h, s, s, d)
    seg = jnp.asarray(_segments([17, 60, 51], s))[None, :]
    o1, lse1 = flash_fwd(q, k, v, causal=True, segment_ids=seg,
                         block_q=32, block_kv=32, interpret=True)
    o2, lse2 = flash_fwd(q, k, v, causal=True, segment_ids=seg,
                         block_q=128, block_kv=64, interpret=True)
    assert max_err(o1, o2) < 1e-5
    assert max_err(lse1, lse2) < 1e-5


def test_varlen_suffix_query_chunked_prefill(rng_key):
    """sq < skv (chunked prefill): q takes the kv suffix's segment ids."""
    b, h, sq, skv, d = 1, 2, 64, 128, 32
    q, k, v, _ = make_qkv(rng_key, b, h, h, sq, skv, d)
    seg = jnp.asarray(_segments([80, 48], skv))[None, :]
    o, _ = flash_fwd(q, k, v, causal=True, segment_ids=seg,
                     block_q=32, block_kv=32, interpret=True)
    o_ref = naive_mha(q, k, v, causal=True, segment_ids=seg)
    assert max_err(o, o_ref) < 1e-3


def test_varlen_with_dropout_matches_across_impls(rng_key):
    """Dropout composes with segment masking identically on every impl."""
    b, h, s, d = 1, 2, 64, 32
    q, k, v, _ = make_qkv(rng_key, b, h, h, s, s, d)
    seg = jnp.asarray(_segments([30, 34], s))[None, :]
    outs = [spark_attention(q, k, v, impl=impl, causal=True, segment_ids=seg,
                            dropout_rate=0.2, seed=5, block_q=32, block_kv=32,
                            xla_chunk=32)
            for impl in IMPLS]
    assert max_err(outs[0], outs[1]) < 1e-5
    assert max_err(outs[0], outs[2]) < 1e-5


def test_varlen_packed_training_smoke(rng_key):
    """A packed batch trains end-to-end: finite loss, finite grads, and the
    loss ignores segment-boundary predictions."""
    from repro import configs
    from repro.data import DataConfig, make_batch
    from repro.models import lm
    from repro.models.layers import Ctx

    cfg = dataclasses.replace(configs.smoke_config("granite_3_2b"),
                              dtype=jnp.float32, remat=False, num_layers=2,
                              d_model=64, num_heads=2, num_kv_heads=2, d_ff=128)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=2,
                    pack=True, min_seg_len=8, max_seg_len=24)
    batch = {k2: jnp.asarray(v2) for k2, v2 in make_batch(dc, 0).items()}
    assert batch["segment_ids"].shape == (2, 64)
    # positions restart at each segment boundary
    seg0 = np.asarray(batch["segment_ids"][0])
    pos0 = np.asarray(batch["positions"][0])
    starts = np.where(np.diff(seg0) != 0)[0] + 1
    assert (pos0[starts] == 0).all() and pos0[0] == 0

    params, _ = lm.init_params(cfg, rng_key)
    ctx = Ctx(impl="xla", xla_chunk=32)
    loss, _ = lm.loss_fn(cfg, params, batch, ctx)
    assert bool(jnp.isfinite(loss))
    grads = jax.grad(lambda p: lm.loss_fn(cfg, p, batch, ctx)[0])(params)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))


def test_varlen_packed_forward_equals_separate_docs(rng_key):
    """Packed forward of two documents ≡ two independent forwards (the whole
    plumbing: segment-masked attention + per-segment RoPE positions)."""
    from repro import configs
    from repro.models import lm
    from repro.models.layers import Ctx

    cfg = dataclasses.replace(configs.smoke_config("granite_3_2b"),
                              dtype=jnp.float32, remat=False, num_layers=2,
                              d_model=64, num_heads=2, num_kv_heads=2, d_ff=128)
    params, _ = lm.init_params(cfg, rng_key)
    ctx = Ctx(impl="xla", xla_chunk=16)
    k1, k2 = jax.random.split(rng_key)
    t1 = jax.random.randint(k1, (1, 24), 0, cfg.vocab_size)
    t2 = jax.random.randint(k2, (1, 40), 0, cfg.vocab_size)
    packed = jnp.concatenate([t1, t2], axis=1)
    seg = jnp.concatenate([jnp.zeros((1, 24), jnp.int32),
                           jnp.ones((1, 40), jnp.int32)], axis=1)
    pos = jnp.concatenate([jnp.arange(24), jnp.arange(40)])[None, :]
    lp, _, _ = lm.forward(cfg, params, ctx, tokens=packed, segment_ids=seg,
                          positions=pos)
    l1, _, _ = lm.forward(cfg, params, ctx, tokens=t1)
    l2, _, _ = lm.forward(cfg, params, ctx, tokens=t2)
    assert max_err(lp[:, :24], l1) < 2e-4
    assert max_err(lp[:, 24:], l2) < 2e-4


# Shape-chosen tiles (None) against explicit 128×128 tiles and the naive
# oracle, forward and gradients, on 1200-token packed rows: the shape-chosen
# tiles are two 640-token blocks, the second one padded.
TILE_LAYOUTS = {
    "short_docs": [40, 30, 70, 25, 90, 60, 45, 80, 50, 65, 85, 100, 75, 120,
                   95, 55, 115],
    "straddling_docs": [300, 450, 250, 200],
    "padding_and_masked_q_block": [400, 200],   # 600-1199 padding: whole
}                                               # q blocks of it at both tiles


@pytest.mark.parametrize("layout", sorted(TILE_LAYOUTS))
def test_varlen_shape_chosen_tiles(rng_key, layout):
    b, hq, hkv, s, d = 1, 4, 2, 1200, 32
    q, k, v, do = make_qkv(rng_key, b, hq, hkv, s, s, d)
    seg = jnp.asarray(_segments(TILE_LAYOUTS[layout], s, pad=True))[None, :]

    def run(f):
        def loss(q, k, v):
            o = f(q, k, v)
            return (o * do).sum(), o
        (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
        return (o,) + g

    kw = dict(impl="pallas_interpret", causal=True, segment_ids=seg)
    got = run(lambda q, k, v: spark_attention(q, k, v, **kw))
    got128 = run(lambda q, k, v: spark_attention(q, k, v, block_q=128,
                                                 block_kv=128, **kw))
    ref = run(lambda q, k, v: naive_mha(q, k, v, causal=True,
                                        segment_ids=seg))
    for a, a128, r in zip(got, got128, ref):
        assert max_err(a, a128) < 1e-5
        assert max_err(a, r) < 1e-3
    pad = np.asarray(seg[0]) < 0
    for a in got:                 # padding: zero output, zero gradient
        assert not pad.any() or float(jnp.abs(a[:, :, pad]).max()) == 0.0
