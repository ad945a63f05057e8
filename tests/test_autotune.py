"""Split-KV launch-parameter autotuner (perf/autotune.py).

Contracts: a pure cost-model plan is always valid (no device, no sweep); the
model prefers splitting exactly where the ROADMAP says the machine idles
(long caches × small ``B·Hkv``) and leaves well-occupied shapes alone; the
persistent cache round-trips through JSON, is keyed by the device kind and
the full decode geometry, and survives corrupt files; the sweep hook
overrides the model; and the serving engine actually bakes the planned split count into its
decode step.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.perf.autotune import (AutotuneCache, DecodeShape, LaunchPlan,
                                 candidate_plans, plan_decode, predict_time)


LONG_SMALL_BATCH = DecodeShape(batch=1, hkv=2, group=4, kv_len=500_000,
                               head_dim=128)


def _assert_valid(shape, plan):
    nk = -(-shape.kv_len // plan.block_kv)
    assert plan.num_splits >= 1
    assert plan.num_splits <= nk          # every split owns >= 1 KV block
    assert plan.block_kv >= 1
    if shape.page_size > 0:
        assert plan.block_kv == shape.page_size
    assert plan.time_s > 0 and np.isfinite(plan.time_s)


def test_pure_cost_model_plans_are_valid():
    shapes = [
        LONG_SMALL_BATCH,
        DecodeShape(batch=32, hkv=8, group=4, kv_len=2048, head_dim=128),
        DecodeShape(batch=2, hkv=2, group=4, kv_len=32768, head_dim=128,
                    page_size=16),
        DecodeShape(batch=1, hkv=1, group=8, kv_len=64, head_dim=64),
        DecodeShape(batch=1, hkv=1, group=1, kv_len=3, head_dim=64),
    ]
    for shape in shapes:
        plan = plan_decode(shape)          # no sweep, no cache, no device
        _assert_valid(shape, plan)
        assert plan.source == "model"


def test_cost_model_splits_where_occupancy_is_low():
    """long_500k at B·Hkv=2 must split; a saturated batch must not."""
    assert plan_decode(LONG_SMALL_BATCH).num_splits > 1
    busy = DecodeShape(batch=64, hkv=8, group=4, kv_len=2048, head_dim=128)
    assert plan_decode(busy).num_splits == 1
    tiny = DecodeShape(batch=1, hkv=1, group=8, kv_len=64, head_dim=64)
    assert plan_decode(tiny).num_splits == 1   # merge overhead dominates


def test_predict_time_monotonic_in_traffic():
    s1 = dataclasses.replace(LONG_SMALL_BATCH, kv_len=10_000)
    s2 = dataclasses.replace(LONG_SMALL_BATCH, kv_len=100_000)
    assert predict_time(s2, 1, 512) > predict_time(s1, 1, 512)


def test_candidates_respect_page_size():
    paged = DecodeShape(batch=2, hkv=2, group=2, kv_len=4096, head_dim=64,
                        page_size=32)
    assert {bk for _, bk in candidate_plans(paged)} == {32}
    contig = DecodeShape(batch=2, hkv=2, group=2, kv_len=4096, head_dim=64)
    assert all(bk <= 4096 for _, bk in candidate_plans(contig))


def test_cache_round_trips_and_is_shape_keyed(tmp_path):
    path = tmp_path / "autotune.json"
    cache = AutotuneCache(path)
    s1 = LONG_SMALL_BATCH
    s2 = dataclasses.replace(s1, batch=2)              # differs in one field
    p1 = plan_decode(s1, cache=cache)
    p2 = plan_decode(s2, cache=cache)
    cache.save()
    assert json.loads(path.read_text())                # valid JSON on disk
    reloaded = AutotuneCache(path)
    h1, h2 = reloaded.get(s1), reloaded.get(s2)
    assert h1 is not None and h2 is not None
    assert (h1.num_splits, h1.block_kv) == (p1.num_splits, p1.block_kv)
    assert (h2.num_splits, h2.block_kv) == (p2.num_splits, p2.block_kv)
    assert h1.source == "cache"
    # a hit short-circuits the model: plan_decode returns the cached record
    assert plan_decode(s1, cache=reloaded).source == "cache"
    # distinct geometries never collide
    assert s1.key() != s2.key()


def test_cache_is_keyed_by_device_kind(tmp_path):
    """A plan recorded on another device kind (say a TPU) is never served on
    this one (the CPU here); both kinds' plans share one file."""
    path = tmp_path / "autotune.json"
    foreign = f"TPU v5 lite/{LONG_SMALL_BATCH.key()}"
    path.write_text(json.dumps({foreign: {"num_splits": 7, "block_kv": 128,
                                          "time_s": 1e-3}}))
    here = AutotuneCache(path)
    assert here.device_kind == jax.devices()[0].device_kind != "TPU v5 lite"
    assert here.get(LONG_SMALL_BATCH) is None
    assert plan_decode(LONG_SMALL_BATCH, cache=here).source == "model"
    here.save()
    saved = json.loads(path.read_text())
    assert len(saved) == 2 and saved[foreign]["num_splits"] == 7
    assert AutotuneCache(path).get(LONG_SMALL_BATCH).source == "cache"


def test_cache_env_override_and_corrupt_file(tmp_path, monkeypatch):
    env_path = tmp_path / "via_env.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(env_path))
    assert AutotuneCache.default_path() == env_path
    env_path.write_text("{not json")
    cache = AutotuneCache()                            # corrupt → empty, no raise
    assert cache.get(LONG_SMALL_BATCH) is None
    plan_decode(LONG_SMALL_BATCH, cache=cache)
    cache.save()
    assert json.loads(env_path.read_text())


def test_sweep_hook_overrides_model(tmp_path):
    """The measured time ranks the model's shortlist, not the model."""
    times = {}

    def sweep(ns, bk):
        # invert the model's preference: make bigger splits "measure" slower
        times[(ns, bk)] = float(ns)
        return times[(ns, bk)]
    plan = plan_decode(LONG_SMALL_BATCH, sweep=sweep)
    assert times, "sweep was never invoked"
    assert plan.source == "sweep"
    best = min(times, key=times.get)
    assert (plan.num_splits, plan.block_kv) == best    # measurement won
    assert plan.num_splits == min(ns for ns, _ in times)


def test_engine_autotune_wires_plan(tmp_path, monkeypatch):
    """ServingEngine(autotune=True) bakes the planned split count in and
    still serves; the plan lands in the persistent cache."""
    from repro import configs
    from repro.models import lm
    from repro.serving import PagedCacheConfig, ServingEngine

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    cfg = dataclasses.replace(configs.smoke_config("qwen3_14b"),
                              dtype=jnp.float32, remat=False)
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    pcfg = PagedCacheConfig(page_size=4, num_pages=16, max_batch=2,
                            max_pages_per_seq=6)
    eng = ServingEngine(cfg, pcfg, params, impl="xla", prefill_len=24,
                        xla_chunk=16, autotune=True)
    shape = DecodeShape(batch=pcfg.max_batch, hkv=cfg.num_kv_heads,
                        group=cfg.num_heads // cfg.num_kv_heads,
                        kv_len=pcfg.max_pages_per_seq * pcfg.page_size,
                        head_dim=cfg.head_dim, page_size=pcfg.page_size,
                        dtype_bytes=jnp.dtype(cfg.dtype).itemsize)
    assert eng.num_splits == plan_decode(shape).num_splits
    assert AutotuneCache().get(shape) is not None       # persisted
    rs = np.random.RandomState(0)
    out, _ = eng.run([(rs.randint(0, cfg.vocab_size, size=8), 4)])
    assert len(out[0]) == 4
    # an explicit num_splits beats autotune
    eng2 = ServingEngine(cfg, pcfg, params, impl="xla", prefill_len=24,
                         xla_chunk=16, autotune=True, num_splits=2)
    assert eng2.num_splits == 2
