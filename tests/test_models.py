"""Per-arch smoke tests (reduced configs) + decode-parity + MoE dispatch."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import max_err
from repro import configs
from repro.models import lm, moe
from repro.models.layers import Ctx


def _f32(cfg):
    return dataclasses.replace(cfg, dtype=jnp.float32, remat=False)


# heavyweight smoke configs (wide recurrences / vision frontends / MoE /
# redundant dense geometries) cost 3-11s apiece on CPU — slow tier. The
# default run keeps granite (the canonical dense arch) only; MoE *math* stays
# covered by the dispatch unit tests below, and every other arch (incl. the
# qwen3 qk_norm variant) runs in the slow tier / CI slow job.
_HEAVY = {"recurrentgemma_2b", "llava_next_34b", "falcon_mamba_7b",
          "dbrx_132b", "hubert_xlarge", "deepseek_moe_16b", "deepseek_67b",
          "deepseek_coder_33b", "qwen3_14b"}


def _arch_params(names):
    return [pytest.param(n, marks=pytest.mark.slow) if n in _HEAVY else n
            for n in names]


def _batch(key, cfg, b=2, s=64):
    batch = {}
    if cfg.frontend:
        batch["embeds"] = jax.random.normal(key, (b, s, lm.FRONTEND_DIM))
    else:
        batch["tokens"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    batch["labels"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("name", _arch_params(configs.ARCHS))
def test_arch_smoke_train_step_shapes_and_finite(rng_key, name):
    """One forward/loss step on CPU: output shapes + no NaNs (assignment req)."""
    cfg = _f32(configs.smoke_config(name))
    params, specs = lm.init_params(cfg, rng_key)
    # specs mirror params structure
    assert set(jax.tree.structure(params).node_data()[1] or []) == \
        set(jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, tuple)
            ).node_data()[1] or [])
    batch = _batch(rng_key, cfg)
    ctx = Ctx(impl="xla", xla_chunk=32, block_q=32, block_kv=32)
    logits, _, _ = lm.forward(cfg, params, ctx, tokens=batch.get("tokens"),
                              embeds=batch.get("embeds"))
    assert logits.shape[:2] == (2, 64)
    assert logits.shape[2] >= cfg.vocab_size
    loss, metrics = lm.loss_fn(cfg, params, batch, ctx)
    assert bool(jnp.isfinite(loss))
    grads = jax.grad(lambda p: lm.loss_fn(cfg, p, batch, ctx)[0])(params)
    flat = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat)


@pytest.mark.parametrize("name", _arch_params(
    [a for a in configs.ARCHS if configs.smoke_config(a).has_decode]))
def test_arch_decode_parity(rng_key, name):
    """prefill + step-by-step decode ≡ teacher-forced forward logits."""
    cfg = _f32(configs.smoke_config(name))
    if cfg.moe is not None:  # avoid capacity drops (train-only semantics)
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params, _ = lm.init_params(cfg, rng_key)
    b, s_prompt, n_gen = 2, 32, 4
    s_total = s_prompt + n_gen
    tokens = jax.random.randint(rng_key, (b, s_total), 0, cfg.vocab_size)
    ctx = Ctx(impl="xla", xla_chunk=16, block_q=16, block_kv=16)
    logits_full, _, _ = lm.forward(cfg, params, ctx, tokens=tokens)
    caches = lm.init_cache(cfg, b, s_total)
    last, caches = lm.prefill(cfg, params, ctx, tokens=tokens[:, :s_prompt],
                              caches=caches)
    assert max_err(last, logits_full[:, s_prompt - 1]) < 2e-4
    for t in range(n_gen):
        pos = s_prompt + t
        lg, caches = lm.decode_step(cfg, params, ctx, tokens[:, pos], caches,
                                    pos)
        assert max_err(lg, logits_full[:, pos]) < 2e-4, f"step {t}"


@pytest.mark.slow  # 64-token decode loop over the hybrid stack (~18s)
def test_sliding_window_ring_cache(rng_key):
    """recurrentgemma ring cache: decode far past the window stays correct."""
    cfg = _f32(configs.smoke_config("recurrentgemma_2b"))
    # window 32 (from smoke cfg); decode 16 tokens past a 48-token prompt so the
    # ring wraps. Compare against teacher-forced full forward.
    params, _ = lm.init_params(cfg, rng_key)
    b, s_prompt, n_gen = 1, 48, 16
    tokens = jax.random.randint(rng_key, (b, s_prompt + n_gen), 0,
                                cfg.vocab_size)
    ctx = Ctx(impl="xla", xla_chunk=16, block_q=16, block_kv=16)
    logits_full, _, _ = lm.forward(cfg, params, ctx, tokens=tokens)
    caches = lm.init_cache(cfg, b, s_prompt + n_gen)
    _, caches = lm.prefill(cfg, params, ctx, tokens=tokens[:, :s_prompt],
                           caches=caches)
    for t in range(n_gen):
        pos = s_prompt + t
        lg, caches = lm.decode_step(cfg, params, ctx, tokens[:, pos], caches,
                                    pos)
        assert max_err(lg, logits_full[:, pos]) < 2e-4, f"step {t}"
    # the attention cache stayed at window size, not prompt+gen size
    k_shapes = [x.shape for x in jax.tree.leaves(caches)
                if hasattr(x, "ndim") and x.ndim == 5]  # stacked [n_super,B,H,S,D]
    assert k_shapes and all(s[3] == cfg.attn_window for s in k_shapes), k_shapes


@pytest.mark.parametrize("name", [pytest.param("dbrx_132b",
                                               marks=pytest.mark.slow),
                                  "deepseek_moe_16b"])
def test_moe_dispatch_matches_dense_oracle(rng_key, name):
    """GShard grouped-einsum dispatch ≡ dense per-expert loop (no drops)."""
    cfg = _f32(configs.smoke_config(name))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    p, _ = moe.init_moe(rng_key, cfg, jnp.float32)
    x = jax.random.normal(rng_key, (2, 64, cfg.d_model))
    out, metrics = moe.apply_moe(p, x, Ctx(), cfg)
    ref = moe.moe_reference(p, x, cfg)
    assert max_err(out, ref) < 1e-5
    assert float(metrics["moe_dropped"]) < 1e-6


def test_moe_capacity_drops_bounded(rng_key):
    """At cf=1.0 with random routing some tokens drop, but the fraction must
    stay well below 50% and the layer must stay finite."""
    cfg = _f32(configs.smoke_config("deepseek_moe_16b"))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.0))
    p, _ = moe.init_moe(rng_key, cfg, jnp.float32)
    x = jax.random.normal(rng_key, (2, 128, cfg.d_model))
    out, metrics = moe.apply_moe(p, x, Ctx(), cfg)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert 0.0 <= float(metrics["moe_dropped"]) < 0.5


@pytest.mark.slow  # two full loss+grad compiles of the granite stack
def test_remat_matches_no_remat(rng_key):
    """jax.checkpoint on superblocks must not change values or grads."""
    cfg0 = dataclasses.replace(configs.smoke_config("granite_3_2b"),
                               dtype=jnp.float32, remat=False)
    cfg1 = dataclasses.replace(cfg0, remat=True)
    params, _ = lm.init_params(cfg0, rng_key)
    batch = _batch(rng_key, cfg0)
    ctx = Ctx(impl="xla", xla_chunk=32)
    l0, g0 = jax.value_and_grad(lambda p: lm.loss_fn(cfg0, p, batch, ctx)[0])(params)
    l1, g1 = jax.value_and_grad(lambda p: lm.loss_fn(cfg1, p, batch, ctx)[0])(params)
    assert max_err(l0, l1) < 1e-6
    assert max(max_err(a, b) for a, b in zip(jax.tree.leaves(g0),
                                             jax.tree.leaves(g1))) < 1e-5


def test_vocab_padding(rng_key):
    """vocab_pad_to pads the embedding/head; loss masks the padding."""
    cfg = _f32(configs.smoke_config("granite_3_2b"))  # vocab 251 (odd)
    params, _ = lm.init_params(cfg, rng_key, vocab_pad_to=16)
    assert params["embed"].shape[0] == 256
    batch = _batch(rng_key, cfg)
    loss, _ = lm.loss_fn(cfg, params, batch, Ctx(impl="xla", xla_chunk=32))
    assert bool(jnp.isfinite(loss))


@pytest.mark.parametrize("name", ["granite_3_2b", "recurrentgemma_2b"])
def test_stacked_init_equals_per_layer_init(rng_key, name):
    """init_params vmaps each stacked sub-block's init; every layer must get
    exactly the values a per-layer init from its own key gives."""
    cfg = configs.smoke_config(name)
    period = len(cfg.block_pattern)
    assert cfg.num_layers >= period
    params, _ = lm.init_params(cfg, rng_key)
    keys = jax.random.split(rng_key, 4 + cfg.num_layers)
    for i in range(cfg.num_layers // period * period):
        kind = cfg.block_pattern[i % period]
        want, _ = lm._init_block(keys[4 + i], cfg, kind, cfg.dtype)
        got = jax.tree.map(lambda x: x[i // period],
                           params["blocks"][f"sub_{i % period}"])
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))


# ---------------------------------------------------------------------------
# recurrent mixers: full-sequence scan ≡ T sequential decode steps
# ---------------------------------------------------------------------------
# The serving packed-prefill path leans on this equivalence (a span's scan
# must leave exactly the state a step-by-step decode would) — pin it at the
# mixer level where a failure localizes to one recurrence, not a whole LM.

def test_rglru_step_equals_scan(rng_key):
    from repro.models import rglru
    cfg = _f32(configs.smoke_config("recurrentgemma_2b"))
    p, _ = rglru.init_rglru(rng_key, cfg, jnp.float32)
    b, s = 2, 12
    x = jax.random.normal(jax.random.fold_in(rng_key, 1), (b, s, cfg.d_model))
    ctx = Ctx(impl="xla")
    out_scan, cache_scan = rglru.apply_rglru(
        p, x, ctx, cfg, cache=rglru.init_rglru_cache(cfg, b))
    cache = rglru.init_rglru_cache(cfg, b)
    ctx_d = dataclasses.replace(ctx, decode=True)
    for t in range(s):
        out_t, cache = rglru.apply_rglru(p, x[:, t:t + 1], ctx_d, cfg,
                                         cache=cache)
        assert max_err(out_t[:, 0], out_scan[:, t]) < 2e-5, f"step {t}"
    assert max_err(cache["h"], cache_scan["h"]) < 2e-5
    assert max_err(cache["conv"], cache_scan["conv"]) < 2e-5


def test_mamba_step_equals_scan(rng_key):
    from repro.models import mamba
    cfg = _f32(configs.smoke_config("falcon_mamba_7b"))
    p, _ = mamba.init_mamba(rng_key, cfg, jnp.float32)
    b, s = 2, 12
    x = jax.random.normal(jax.random.fold_in(rng_key, 1), (b, s, cfg.d_model))
    ctx = Ctx(impl="xla")
    out_scan, cache_scan = mamba.apply_mamba(
        p, x, ctx, cfg, cache=mamba.init_mamba_cache(cfg, b))
    cache = mamba.init_mamba_cache(cfg, b)
    ctx_d = dataclasses.replace(ctx, decode=True)
    for t in range(s):
        out_t, cache = mamba.apply_mamba(p, x[:, t:t + 1], ctx_d, cfg,
                                         cache=cache)
        assert max_err(out_t[:, 0], out_scan[:, t]) < 2e-5, f"step {t}"
    assert max_err(cache["h"], cache_scan["h"]) < 2e-5
    assert max_err(cache["conv"], cache_scan["conv"]) < 2e-5
