"""MHA-Forward Pallas kernel vs. the pure-jnp oracle (interpret mode).

Sweeps shapes × dtypes × masking modes × accumulate precisions, mirroring the
paper's §4.2.3 accuracy methodology (oracle = f32 unfused attention).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_qkv, max_err
from repro.kernels.flash_fwd import flash_fwd
from repro.kernels.ref import naive_mha, online_mha

_BIG = pytest.mark.slow  # 256+-seq interpret sweeps: slow tier
CASES = [
    # b, hq, hkv, sq, skv, d, causal, window, bq, bkv
    pytest.param((2, 4, 4, 256, 256, 64, False, None, 128, 128), marks=_BIG),
    (2, 4, 2, 256, 256, 64, True, None, 128, 128),
    (1, 8, 1, 128, 128, 128, True, None, 64, 64),      # MQA
    (1, 2, 1, 128, 384, 128, True, None, 64, 128),     # suffix query (chunked prefill)
    (1, 2, 2, 256, 256, 64, True, 64, 64, 64),         # sliding window
    pytest.param((1, 2, 2, 256, 256, 64, False, 128, 128, 128),
                 marks=_BIG),                          # window, non-causal
    (1, 2, 2, 200, 200, 64, True, None, 128, 128),     # pad: seq not divisible
    pytest.param((1, 2, 2, 192, 320, 80, False, None, 64, 64),
                 marks=_BIG),                          # head_dim 80 (hubert)
    (1, 1, 1, 64, 64, 256, True, None, 64, 64),        # head_dim 256 (recurrentgemma)
    (3, 2, 2, 96, 96, 64, True, None, 32, 32),         # odd batch, small blocks
]


def _ids(cases):
    return [str(getattr(c, "values", (c,))[0]) for c in cases]


@pytest.mark.parametrize("case", CASES, ids=_ids(CASES))
def test_fwd_matches_oracle(rng_key, case):
    b, hq, hkv, sq, skv, d, causal, window, bq, bkv = case
    q, k, v, _ = make_qkv(rng_key, b, hq, hkv, sq, skv, d)
    o, lse = flash_fwd(q, k, v, causal=causal, window=window,
                       block_q=bq, block_kv=bkv, interpret=True)
    o_ref, lse_ref = naive_mha(q, k, v, causal=causal, window=window,
                               return_residuals=True)
    assert o.shape == (b, hq, sq, d)
    assert max_err(o, o_ref) < 2e-5
    assert max_err(lse, lse_ref) < 2e-5


@pytest.mark.parametrize("case", CASES[:4], ids=_ids(CASES[:4]))
def test_online_xla_matches_oracle(rng_key, case):
    """The dry-run XLA path implements the identical algorithm."""
    b, hq, hkv, sq, skv, d, causal, window, bq, bkv = case
    q, k, v, _ = make_qkv(rng_key, b, hq, hkv, sq, skv, d)
    o = online_mha(q, k, v, causal=causal, window=window, chunk=64)
    o_ref = naive_mha(q, k, v, causal=causal, window=window)
    assert max_err(o, o_ref) < 2e-5


def test_bf16_acc_variant(rng_key):
    """Paper's FP16-ACC analogue: matmuls accumulate in bf16; softmax stays f32."""
    q, k, v, _ = make_qkv(rng_key, 2, 4, 4, 256, 256, 64, dtype=jnp.bfloat16)
    o16, _ = flash_fwd(q, k, v, acc_dtype=jnp.bfloat16, interpret=True)
    o32, _ = flash_fwd(q, k, v, acc_dtype=jnp.float32, interpret=True)
    o_ref = naive_mha(q.astype(jnp.float32), k.astype(jnp.float32),
                      v.astype(jnp.float32))
    # bf16-ACC is less accurate than f32-ACC but must stay within bf16 roundoff
    assert max_err(o16, o_ref) < 0.05
    assert max_err(o32, o_ref) <= max_err(o16, o_ref) + 1e-6


def test_dropout_matches_oracle_mask(rng_key):
    """In-kernel dropout regenerates exactly the oracle's coordinate-hash mask."""
    q, k, v, _ = make_qkv(rng_key, 1, 2, 2, 128, 128, 64)
    o, _ = flash_fwd(q, k, v, dropout_rate=0.1, dropout_seed=7,
                     block_q=64, block_kv=64, interpret=True)
    o_ref = naive_mha(q, k, v, dropout_rate=0.1, dropout_seed=7)
    assert max_err(o, o_ref) < 2e-5


def test_dropout_block_decomposition_invariance(rng_key):
    """Masks derive from global coordinates → block size must not change them."""
    q, k, v, _ = make_qkv(rng_key, 1, 2, 2, 256, 256, 64)
    o1, _ = flash_fwd(q, k, v, dropout_rate=0.2, dropout_seed=3,
                      block_q=64, block_kv=64, interpret=True)
    o2, _ = flash_fwd(q, k, v, dropout_rate=0.2, dropout_seed=3,
                      block_q=128, block_kv=32, interpret=True)
    assert max_err(o1, o2) < 1e-5


def test_fully_masked_rows_are_zero():
    """window=1 + suffix offset can fully mask rows; output must be 0, not NaN."""
    q = jnp.ones((1, 1, 64, 64))
    k = jnp.ones((1, 1, 64, 64))
    v = jnp.ones((1, 1, 64, 64))
    # non-causal with a window that excludes everything for early rows is not
    # constructible; instead use causal + tiny window and check no NaNs anywhere
    o, lse = flash_fwd(q, k, v, causal=True, window=1, interpret=True)
    assert not bool(jnp.isnan(o).any())
    assert not bool(jnp.isnan(lse).any())


# Shape-chosen tiles (block_q/block_kv None) against explicit 128×128 tiles
# and the oracle, at lengths that give several shape-chosen blocks
# (1200 → two 640-token blocks, the second one padded; f32 rows of D=128
# are wide, so 640 → two 384-token blocks).
TILE_CASES = {
    # b, hq, hkv, sq, skv, d, causal, window, dropout
    "gqa_32_8": (1, 32, 8, 640, 640, 128, True, None, 0.0),
    "window": (1, 2, 2, 1200, 1200, 64, True, 200, 0.0),
    "suffix_q": (1, 2, 1, 256, 1200, 64, True, None, 0.0),
    "dropout": (1, 2, 2, 1200, 1200, 64, False, None, 0.2),
}


@pytest.mark.parametrize("case", sorted(TILE_CASES))
def test_fwd_shape_chosen_tiles(rng_key, case):
    b, hq, hkv, sq, skv, d, causal, window, drop = TILE_CASES[case]
    q, k, v, _ = make_qkv(rng_key, b, hq, hkv, sq, skv, d)
    kw = dict(causal=causal, window=window, dropout_rate=drop,
              dropout_seed=9, interpret=True)
    o, lse = flash_fwd(q, k, v, **kw)
    o128, lse128 = flash_fwd(q, k, v, block_q=128, block_kv=128, **kw)
    o_ref, lse_ref = naive_mha(q, k, v, causal=causal, window=window,
                               dropout_rate=drop, dropout_seed=9,
                               return_residuals=True)
    assert max_err(o, o128) < 2e-5 and max_err(lse, lse128) < 2e-5
    assert max_err(o, o_ref) < 2e-5 and max_err(lse, lse_ref) < 2e-5
