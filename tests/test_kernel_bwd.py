"""MHA-Backward dual-pass kernels vs. jax.grad of the oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_qkv, max_err
from repro.kernels.flash_fwd import flash_fwd
from repro.kernels.flash_bwd import flash_bwd
from repro.kernels.ops import mha, AttnConfig
from repro.kernels.ref import naive_mha

_BIG = pytest.mark.slow  # 256+-seq dual-pass interpret sweeps: slow tier
CASES = [
    # b, hq, hkv, sq, skv, d, causal, window, drop
    pytest.param((2, 2, 2, 256, 256, 64, False, None, 0.0), marks=_BIG),
    pytest.param((2, 4, 2, 256, 256, 64, True, None, 0.0),
                 marks=_BIG),                    # GQA group-sum of dK/dV
                                                 # (group-sum also default-
                                                 # covered by test_edge_cases)
    (1, 2, 1, 128, 384, 128, True, None, 0.0),   # suffix query
    pytest.param((1, 2, 2, 256, 256, 64, True, 64, 0.0),
                 marks=_BIG),                    # sliding window
    (1, 2, 2, 200, 200, 64, True, None, 0.0),    # padding
    (1, 2, 2, 128, 128, 64, False, None, 0.15),  # dropout replay in recompute
    (1, 2, 2, 128, 128, 80, True, None, 0.0),    # head_dim 80
]


def _ref_grads(q, k, v, do, causal, window, drop):
    def f(q, k, v):
        o = naive_mha(q, k, v, causal=causal, window=window,
                      dropout_rate=drop, dropout_seed=3)
        return (o * do).sum()
    return jax.grad(f, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("case", CASES,
                         ids=[str(getattr(c, "values", (c,))[0])
                              for c in CASES])
def test_bwd_matches_oracle_grads(rng_key, case):
    b, hq, hkv, sq, skv, d, causal, window, drop = case
    q, k, v, do = make_qkv(rng_key, b, hq, hkv, sq, skv, d)
    dq_r, dk_r, dv_r = _ref_grads(q, k, v, do, causal, window, drop)
    o, lse = flash_fwd(q, k, v, causal=causal, window=window,
                       dropout_rate=drop, dropout_seed=3,
                       block_q=64, block_kv=64, interpret=True)
    dq, dk, dv = flash_bwd(q, k, v, o, lse, do, causal=causal, window=window,
                           dropout_rate=drop, dropout_seed=3,
                           block_q=64, block_kv=64, interpret=True)
    assert max_err(dq, dq_r) < 5e-5
    assert max_err(dk, dk_r) < 5e-5
    assert max_err(dv, dv_r) < 5e-5
    assert dk.shape == k.shape and dv.shape == v.shape


def test_custom_vjp_under_jit(rng_key):
    """The paper's pybind11-into-PyTorch glue, JAX-style: grad-of-jit works."""
    q, k, v, do = make_qkv(rng_key, 2, 4, 2, 128, 128, 64)
    cfg = AttnConfig(causal=True, block_q=64, block_kv=64, interpret=True)

    @jax.jit
    def loss(q, k, v, seed):
        return (mha(q, k, v, seed=seed, config=cfg) * do).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, jnp.int32(0))
    dq_r, dk_r, dv_r = _ref_grads(q, k, v, do, True, None, 0.0)
    assert max_err(g[0], dq_r) < 5e-5
    assert max_err(g[1], dk_r) < 5e-5
    assert max_err(g[2], dv_r) < 5e-5


def test_bwd_bf16_acc(rng_key):
    """Paper: backward offered in FP16-ACC only ('does not require high
    precision'). bf16-ACC grads must stay within bf16 roundoff of the oracle."""
    q, k, v, do = make_qkv(rng_key, 1, 2, 2, 128, 128, 64, dtype=jnp.bfloat16)
    qf, kf, vf, dof = (x.astype(jnp.float32) for x in (q, k, v, do))
    dq_r, dk_r, dv_r = _ref_grads(qf, kf, vf, dof, True, None, 0.0)
    o, lse = flash_fwd(q, k, v, causal=True, interpret=True)
    dq, dk, dv = flash_bwd(q, k, v, o, lse, do, causal=True,
                           acc_dtype=jnp.bfloat16, interpret=True)
    assert max_err(dq, dq_r) < 0.35   # bf16 has ~3 decimal digits
    assert max_err(dk, dk_r) < 0.35
    assert max_err(dv, dv_r) < 0.35


def test_dropout_train_eval_consistency(rng_key):
    """Same seed → forward and backward see identical masks (paper §4.2.2)."""
    q, k, v, do = make_qkv(rng_key, 1, 2, 2, 128, 128, 64)
    cfg = AttnConfig(dropout_rate=0.3, block_q=64, block_kv=64, interpret=True)

    def loss(q, k, v):
        return (mha(q, k, v, seed=11, config=cfg) * do).sum()

    # finite-difference check on a single coordinate: only valid if bwd mask
    # matches fwd mask exactly
    g = jax.grad(loss)(q, k, v)
    eps = 1e-3
    e = jnp.zeros_like(q).at[0, 0, 0, 0].set(eps)
    fd = (loss(q + e, k, v) - loss(q - e, k, v)) / (2 * eps)
    assert abs(float(fd) - float(g[0, 0, 0, 0])) < 5e-2


# Shape-chosen tiles (None) against explicit 128×128 tiles and the oracle's
# gradients, at lengths that give several shape-chosen blocks (1200 → two
# 640-token blocks; f32 rows of D=128 are wide, so 640 → two of 384).
TILE_CASES = {
    # b, hq, hkv, sq, skv, d, causal, window, dropout
    "gqa_32_8": (1, 32, 8, 640, 640, 128, True, None, 0.0),
    "window": (1, 2, 2, 1200, 1200, 64, True, 200, 0.0),
    "suffix_q": (1, 2, 1, 256, 1200, 64, True, None, 0.0),
    "dropout": (1, 2, 2, 1200, 1200, 64, False, None, 0.2),
}


@pytest.mark.parametrize("case", sorted(TILE_CASES))
def test_bwd_shape_chosen_tiles(rng_key, case):
    b, hq, hkv, sq, skv, d, causal, window, drop = TILE_CASES[case]
    q, k, v, do = make_qkv(rng_key, b, hq, hkv, sq, skv, d)
    refs = _ref_grads(q, k, v, do, causal, window, drop)
    kw = dict(causal=causal, window=window, dropout_rate=drop,
              dropout_seed=3, interpret=True)
    grads = {}
    for tiles in ({}, dict(block_q=128, block_kv=128)):
        o, lse = flash_fwd(q, k, v, **kw, **tiles)
        grads[len(tiles)] = flash_bwd(q, k, v, o, lse, do, **kw, **tiles)
    for g, g128, r in zip(grads[0], grads[2], refs):
        assert max_err(g, g128) < 5e-5
        assert max_err(g, r) < 5e-5
