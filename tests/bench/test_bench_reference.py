"""The benchmark's plain float32 reference against the program's model at a
smoke size on the CPU, and its float8 control against both."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import gen, harness, reference, weights  # noqa: E402


def tiny(qk_norm=False, dtype="float32"):
    with open(os.path.join(ROOT, "bench", "configs",
                           "granite-3-2b-train.json")) as f:
        c = json.load(f)
    c.update(name="tiny", hidden_size=64, intermediate_size=96,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             num_hidden_layers=2, vocab_size=97, qk_norm=qk_norm,
             torch_dtype=dtype)
    return c


def program_loss(c, params, batch):
    from repro.models import lm
    from repro.models.layers import Ctx
    cfg = harness.program_config(c)
    b = {k: jnp.asarray(v) for k, v in batch.items()}
    return lm.loss_fn(cfg, params, b, Ctx(impl="xla"))[0]


def batch_of(rows, seed=3, vocab=97):
    return gen.pack_batch(rows, np.random.default_rng(seed), vocab,
                          sum(rows[0]))


@pytest.mark.parametrize("qk_norm", [False, True])
def test_reference_loss_and_gradients_match_the_program(qk_norm):
    c = tiny(qk_norm)
    params = weights.make_params(c, 11)
    batch = batch_of([[5, 20, 7], [32]])
    with jax.default_matmul_precision("highest"):
        want, gw = jax.value_and_grad(program_loss, argnums=1)(c, params,
                                                               batch)
        got, gg = jax.value_and_grad(
            lambda p: reference.batch_loss(c, reference.Num(), p,
                                           {k: jnp.asarray(v)
                                            for k, v in batch.items()}))(
            params)
    assert abs(float(got) - float(want)) < 1e-5
    for a, b in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


def test_reference_logits_match_the_program_forward():
    from repro.models import lm
    from repro.models.layers import Ctx
    c = tiny()
    params = weights.make_params(c, 5)
    tokens = np.random.default_rng(0).integers(0, 97, 48).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = lm.forward(harness.program_config(c), params, Ctx(impl="xla"),
                          tokens=jnp.asarray(tokens)[None])[0][0]
        best, served = reference.make_serve_stats(c, 48)(params, tokens)
    want = np.asarray(want)
    np.testing.assert_allclose(best, want.max(-1), rtol=1e-4, atol=1e-5)
    nxt = np.concatenate([tokens[1:], tokens[:1]])
    np.testing.assert_allclose(served, want[np.arange(48), nxt], rtol=1e-4,
                               atol=1e-5)


def test_float8_control_departs_from_the_reference():
    c = tiny()
    params = weights.make_params(c, 5)
    tokens = np.random.default_rng(1).integers(0, 97, 64).astype(np.int32)
    best, served, at_ctl = reference.make_serve_stats(c, 64, True)(params,
                                                                  tokens)
    assert np.all(best >= at_ctl)
    assert np.max(best - at_ctl) > 0          # the control misranks somewhere
    assert np.all(best >= served)


def test_reference_training_readings_track_the_optimizer():
    """Three reference AdamW steps: losses finite, the first clipped
    gradient has unit global norm at most, every leaf moved."""
    c = tiny()
    batches = [batch_of([[16, 16], [32]], seed=s) for s in range(3)]
    losses, g0, change = reference.train_readings(
        c, reference.Num(), lambda: weights.make_params(c, 2), batches, 3)
    assert all(np.isfinite(losses))
    total = np.sqrt(sum(float(np.sum(np.square(v))) for v in g0.values()))
    assert total <= 1.0 + 1e-5
    assert all(np.all(v > 0) for v in change.values())
