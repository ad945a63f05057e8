"""The program's own tracing: engine spans on the profiler's clock, GC spans,
and the per-request host timestamps the engine keeps."""

import dataclasses
import functools
import gc
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.runtime.spans import trace_gc, untrace_gc
from repro.serving import PagedCacheConfig, ServingEngine

ENGINE_SPANS = {"repro.engine.schedule", "repro.engine.prefill",
                "repro.engine.prefill.sample", "repro.engine.decode",
                "repro.engine.decode.sample"}


@functools.lru_cache(maxsize=None)
def _model():
    from repro.models import lm
    cfg = dataclasses.replace(configs.smoke_config("qwen3_14b"),
                              dtype=jnp.float32, remat=False)
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, lens):
    rs = np.random.RandomState(0)
    return [rs.randint(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in lens]


def _host_spans(log_dir, prefix="repro."):
    """(name, start ns, end ns, line, stats) of the host spans in a trace."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns), line.name,
                                dict(e.stats)))
    return out


@pytest.mark.time_limit(120)
def test_engine_spans_nest_inside_each_step(tmp_path):
    cfg, params = _model()
    pcfg = PagedCacheConfig(page_size=4, num_pages=16, max_batch=2,
                            max_pages_per_seq=4)
    eng = ServingEngine(cfg, pcfg, params, impl="xla", prefill_len=16,
                        xla_chunk=16)
    rids = [eng.submit(p, 3) for p in _prompts(cfg, (5, 7, 6))]
    steps = 0
    with jax.profiler.trace(str(tmp_path)):
        while not eng.scheduler.idle:
            eng.step()
            steps += 1
    spans = _host_spans(str(tmp_path))
    outer = [s for s in spans if s[0] == "repro.engine.step"]
    inner = [s for s in spans if s[0] != "repro.engine.step"]
    assert len(outer) == steps
    assert {s[0] for s in inner} == ENGINE_SPANS
    for name, a, b, line, _ in inner:
        assert any(a >= a0 and b <= b0 and line == l0
                   for _, a0, b0, l0, _ in outer), name
    # the prefill rows name their requests
    seen = set()
    for name, _, _, _, stats in spans:
        if name == "repro.engine.prefill":
            seen |= {int(x) for x in stats["rids"].strip("[]").split(",")}
    assert seen == set(rids)


@pytest.mark.time_limit(60)
def test_gc_spans_only_while_installed(tmp_path):
    trace_gc()
    try:
        with jax.profiler.trace(str(tmp_path / "on")):
            gc.collect()
    finally:
        untrace_gc()
    with jax.profiler.trace(str(tmp_path / "off")):
        gc.collect()
    on = _host_spans(str(tmp_path / "on"), "repro.host.gc")
    assert on and all(b >= a for _, a, b, _, _ in on)
    assert {s[4]["generation"] for s in on} >= {2}
    assert not _host_spans(str(tmp_path / "off"), "repro.host.gc")


@pytest.mark.time_limit(120)
def test_request_timestamps_are_monotone_and_survive_preemption():
    """A pool tight enough to preempt: every request's timestamps run
    submitted ≤ admitted ≤ first token ≤ …, one per token, and a preempted
    request keeps the admission time of its first admission."""
    cfg, params = _model()
    # 6 usable pages: lazy admission takes both requests and runs dry growing
    pcfg = PagedCacheConfig(page_size=4, num_pages=7, max_batch=2,
                            max_pages_per_seq=4)
    eng = ServingEngine(cfg, pcfg, params, impl="xla", prefill_len=16,
                        xla_chunk=16, lazy=True)
    for p, n in zip(_prompts(cfg, (9, 5)), (6, 8)):
        eng.submit(p, n)
    first_admit, preempted_at = {}, {}
    while not eng.scheduler.idle:
        eng.step()
        now = time.perf_counter()
        live = {s.request.rid: s for s in eng.scheduler.active.values()}
        times = eng.request_times()
        for rid, seq in live.items():
            first_admit.setdefault(rid, times[rid].admitted_at)
            assert len(times[rid].token_at) == len(seq.all_generated)
        for req in eng.scheduler.waiting:
            if req.generated_prefix:
                preempted_at.setdefault(req.rid, now)
                assert times[req.rid].admitted_at == first_admit[req.rid]
    assert preempted_at, "the pool never ran dry"
    assert eng.scheduler.preemptions >= 1
    for rid, res in eng.results.items():
        t = [res.submitted_at, res.admitted_at, *res.token_at]
        assert None not in t and t == sorted(t), rid
        assert len(res.token_at) == len(res.tokens)
        assert res.admitted_at == first_admit[rid]
        if rid in preempted_at:
            assert res.admitted_at < preempted_at[rid]
    assert eng.request_times()[0].token_at == eng.results[0].token_at


def _watch_utilization(eng, seen):
    """Record every pool reading the engine's decode steps take."""
    util = eng.scheduler.tables.utilization

    def recorded():
        u = util()
        seen.append((u["utilization"], u["pool_fraction"]))
        return u
    eng.scheduler.tables.utilization = recorded


@pytest.mark.time_limit(120)
def test_pool_means_are_running_sums_that_survive_restore():
    cfg, params = _model()
    pcfg = PagedCacheConfig(page_size=4, num_pages=16, max_batch=2,
                            max_pages_per_seq=4)

    def engine():
        return ServingEngine(cfg, pcfg, params, impl="xla", prefill_len=16,
                             xla_chunk=16)

    seen = []
    eng = engine()
    _watch_utilization(eng, seen)
    for p in _prompts(cfg, (5, 7, 6)):
        eng.submit(p, 4)
    for _ in range(3):
        eng.step()
    del eng.scheduler.tables.utilization    # keep the watch out of the copy
    eng2 = engine()
    eng2.restore(eng.snapshot())
    assert (eng2.util_steps, eng2.util_sum, eng2.pool_sum) == \
        (eng.util_steps, eng.util_sum, eng.pool_sum) == \
        (len(seen), sum(u for u, _ in seen), sum(f for _, f in seen))
    _watch_utilization(eng2, seen)     # the restored scheduler's own tables
    _, stats = eng2.run()
    assert stats["mean_utilization"] == pytest.approx(
        np.mean([u for u, _ in seen]), rel=1e-12)
    assert stats["mean_pool_fraction"] == pytest.approx(
        np.mean([f for _, f in seen]), rel=1e-12)
