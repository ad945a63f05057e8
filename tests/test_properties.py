"""Property tests for the system's core invariants.

The online-softmax state algebra (core/online_softmax.py) is the single piece
of math every execution path shares — kernel, XLA fallback, distributed decode
merge. If its invariants hold, block decomposition is sound everywhere.

The invariants are fuzzed with ``hypothesis`` and also run over a fixed
deterministic case grid, a cheap smoke layer with named cases.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import online_softmax as osm
from repro.kernels import rng

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


def _softmax_weighted(s, v):
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return p @ v


def _case(seed, rows, cols, n_blocks, d, scale):
    r = np.random.RandomState(seed)
    s = (r.randn(rows, n_blocks * cols) * scale).astype(np.float32)
    v = r.randn(n_blocks * cols, d).astype(np.float32)
    return s, v, cols


# deterministic grid, a smoke layer beside the fuzzing:
# (seed, rows, cols, n_blocks, d, scale)
DET_CASES = [
    (0, 2, 2, 1, 1, 1.0),
    (1, 4, 8, 3, 4, 0.5),
    (2, 8, 16, 4, 8, 30.0),   # large-magnitude scores
    (3, 3, 5, 2, 7, 10.0),    # odd sizes
    (4, 8, 4, 4, 2, 0.1),
]


@st.composite
def score_blocks(draw):
    rows = draw(st.integers(2, 8))
    cols = draw(st.integers(2, 16))
    n_blocks = draw(st.integers(1, 4))
    d = draw(st.integers(1, 8))
    seed = draw(st.integers(0, 2**31 - 1))
    scale = draw(st.floats(0.1, 30.0))  # exercise large-magnitude scores
    return _case(seed, rows, cols, n_blocks, d, scale)


# ---------------------------------------------------------------------------
# invariant checkers (shared by the fuzzed and deterministic variants)
# ---------------------------------------------------------------------------

def check_blocked_equals_full_softmax(data):
    """Folding blocks sequentially == softmax over the concatenation (Eq. 3)."""
    s, v, cols = data
    rows, total = s.shape
    d = v.shape[1]
    state = osm.init_state((rows,), d)
    for i in range(total // cols):
        state = osm.update(state, jnp.asarray(s[:, i * cols:(i + 1) * cols]),
                           jnp.asarray(v[i * cols:(i + 1) * cols]))
    o, lse = osm.finalize(state)
    o_ref = _softmax_weighted(s, v)
    np.testing.assert_allclose(np.asarray(o), o_ref, atol=1e-4, rtol=1e-4)
    # lse is the true log-sum-exp
    lse_ref = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) + s.max(-1)
    np.testing.assert_allclose(np.asarray(lse), lse_ref, atol=1e-4, rtol=1e-4)


def check_merge_is_order_invariant(data):
    """State merge is commutative+associative → kv blocks can be processed in
    any order (this is what licenses the distributed flash-decode merge)."""
    s, v, cols = data
    rows, total = s.shape
    d = v.shape[1]
    n = total // cols
    states = []
    for i in range(n):
        st_i = osm.init_state((rows,), d)
        st_i = osm.update(st_i, jnp.asarray(s[:, i * cols:(i + 1) * cols]),
                          jnp.asarray(v[i * cols:(i + 1) * cols]))
        states.append(st_i)
    fwd = states[0]
    for st_i in states[1:]:
        fwd = osm.merge(fwd, st_i)
    rev = states[-1]
    for st_i in reversed(states[:-1]):
        rev = osm.merge(rev, st_i)
    o1, l1 = osm.finalize(fwd)
    o2, l2 = osm.finalize(rev)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-4)


def check_vectorized_merge_matches_pairwise(data):
    """merge_many over a stacked axis == any pairwise merge order == the full
    softmax (associativity is what licenses the split-KV decode finalize)."""
    s, v, cols = data
    rows, total = s.shape
    d = v.shape[1]
    n = total // cols
    states = []
    for i in range(n):
        st_i = osm.init_state((rows,), d)
        st_i = osm.update(st_i, jnp.asarray(s[:, i * cols:(i + 1) * cols]),
                          jnp.asarray(v[i * cols:(i + 1) * cols]))
        states.append(st_i)
    stacked = osm.SoftmaxState(m=jnp.stack([x.m for x in states]),
                               l=jnp.stack([x.l for x in states]),
                               acc=jnp.stack([x.acc for x in states]))
    o_vec, lse_vec = osm.finalize(osm.merge_many(stacked, axis=0))
    pair = states[0]
    for st_i in states[1:]:
        pair = osm.merge(pair, st_i)
    o_pair, lse_pair = osm.finalize(pair)
    np.testing.assert_allclose(np.asarray(o_vec), np.asarray(o_pair),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(lse_vec), np.asarray(lse_pair),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(o_vec), _softmax_weighted(s, v),
                               atol=1e-4, rtol=1e-4)
    # an all-empty stack merges to the empty state, NaN-free
    empty = osm.init_state((n, rows), d)
    o_e, lse_e = osm.finalize(osm.merge_many(empty, axis=0))
    assert float(jnp.abs(o_e).max()) == 0.0
    assert not bool(jnp.isnan(lse_e).any())


def check_shift_invariance(shift, data):
    """softmax(s + c) == softmax(s): the max-subtraction must absorb shifts."""
    s, v, cols = data
    rows, total = s.shape
    d = v.shape[1]

    def run(sarr):
        state = osm.init_state((rows,), d)
        state = osm.update(state, jnp.asarray(sarr), jnp.asarray(v))
        return osm.finalize(state)[0]

    o1 = run(s)
    o2 = run(s + np.float32(shift))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)


def check_dropout_rng_statistics(seed, b, h, rate):
    """Keep-rate ≈ 1-rate; mask depends only on coordinates (replayable)."""
    qp = jnp.arange(256, dtype=jnp.int32)[:, None]
    kp = jnp.arange(256, dtype=jnp.int32)[None, :]
    m1 = rng.dropout_keep_mask(rate, seed, b, h, qp, kp)
    m2 = rng.dropout_keep_mask(rate, seed, b, h, qp, kp)
    assert bool(jnp.all(m1 == m2))
    keep = float(jnp.mean(m1))
    assert abs(keep - (1.0 - rate)) < 0.02


def check_dropout_rng_decorrelated_across_heads(seed):
    qp = jnp.arange(128, dtype=jnp.int32)[:, None]
    kp = jnp.arange(128, dtype=jnp.int32)[None, :]
    m_h0 = rng.dropout_keep_mask(0.5, seed, 0, 0, qp, kp)
    m_h1 = rng.dropout_keep_mask(0.5, seed, 0, 1, qp, kp)
    agree = float(jnp.mean(m_h0 == m_h1))
    assert 0.4 < agree < 0.6  # independent masks agree ~half the time


# ---------------------------------------------------------------------------
# hypothesis variants (skipped without hypothesis)
# ---------------------------------------------------------------------------

@given(score_blocks())
def test_blocked_equals_full_softmax(data):
    check_blocked_equals_full_softmax(data)


@given(score_blocks())
def test_merge_is_order_invariant(data):
    check_merge_is_order_invariant(data)


@given(score_blocks())
def test_vectorized_merge_matches_pairwise(data):
    check_vectorized_merge_matches_pairwise(data)


@given(st.floats(-50, 50), score_blocks())
def test_shift_invariance(shift, data):
    check_shift_invariance(shift, data)


@given(st.integers(0, 2**31 - 1), st.integers(0, 63), st.integers(0, 63),
       st.floats(0.05, 0.95))
def test_dropout_rng_statistics(seed, b, h, rate):
    check_dropout_rng_statistics(seed, b, h, rate)


@given(st.integers(0, 2**31 - 1))
def test_dropout_rng_decorrelated_across_heads(seed):
    check_dropout_rng_decorrelated_across_heads(seed)


# ---------------------------------------------------------------------------
# deterministic fallback: always runs, so the invariants are asserted even
# in containers without hypothesis
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", DET_CASES, ids=[str(c) for c in DET_CASES])
def test_det_softmax_state_invariants(case):
    data = _case(*case)
    check_blocked_equals_full_softmax(data)
    check_merge_is_order_invariant(data)
    check_vectorized_merge_matches_pairwise(data)
    check_shift_invariance(17.5, data)
    check_shift_invariance(-3.25, data)


@pytest.mark.parametrize("seed,b,h,rate", [(0, 0, 0, 0.1), (7, 3, 5, 0.5),
                                           (123, 63, 63, 0.9)])
def test_det_dropout_rng(seed, b, h, rate):
    check_dropout_rng_statistics(seed, b, h, rate)
    check_dropout_rng_decorrelated_across_heads(seed)


def test_det_fully_masked_state_is_zero():
    """A state fed only NEG_INF scores finalizes to zeros, not NaN/averages —
    the invariant behind the kernels' fully-masked-row handling (packed pad)."""
    state = osm.init_state((4,), 8)
    s = jnp.full((4, 16), osm.NEG_INF)
    v = jnp.ones((16, 8))
    state = osm.update(state, s, v)
    o, lse = osm.finalize(state)
    assert float(jnp.abs(o).max()) == 0.0
    assert not bool(jnp.isnan(lse).any())
    # a later real block must fully recover (transient garbage is rescaled out)
    state = osm.update(state, jnp.zeros((4, 16)), v)
    o2, _ = osm.finalize(state)
    np.testing.assert_allclose(np.asarray(o2), 1.0, atol=1e-6)
