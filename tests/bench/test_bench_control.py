"""The control at a size a test run holds: the plain reference computed in
float8, the precision below the configurations' bfloat16, put in the
program's place, comes out not correct under each cell's limits; so does
the planted half-batch fault.  On the chip the same readings, at each
cell's own size, set the limits (``bench/control.py``)."""

import os
import sys
import time

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import control, gen, harness  # noqa: E402

M = harness.load_manifest()
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
            vocab_size=251)


def tiny_ctx(cell, mix_update, serving=None, seconds=1.0, **sizes):
    w = harness.cell_entry(M, cell)
    c = dict(harness.load_config(M, w["config"]), **dict(TINY, **sizes))
    if serving:
        c["serving"] = serving
    mix = dict(gen.load_mix(w["traffic"]), **mix_update)
    return harness.RunContext(
        cell=cell, config=c, mix=mix, seed=2 ** 31 + 3, seconds=seconds,
        trace=False, devices=jax.devices(), limits=harness.load_limits(cell),
        t_start=time.perf_counter(), compiles=harness.CompileCounter())


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]
                                  if gen.load_mix(w["traffic"])["kind"]
                                  == "train"])
def test_training_control_is_not_correct(cell):
    ctx = tiny_ctx(cell, dict(row_len=256, rows=2, layouts=3, doc_len={
        "median": 48, "sigma": 1.0, "min": 8, "max": 256}))
    out = control.train_readings(ctx)
    for name in ("control_f8", "half_batch"):
        assert any(out[name][k] > ctx.limits[k] for k in ctx.limits), \
            (name, out[name], ctx.limits)


def test_serving_control_is_not_correct():
    """Every request is due in the first 2.5 s and done long before the
    window closes, so the sample, and so the reading, is the same in every
    run.  Eight layers: the float8 error grows with depth, and at two it
    seldom changes the first-ranked token."""
    from bench.drivers import serve
    ctx = tiny_ctx("granite-serve-chat", dict(
        rate_per_s=4.0, warmup_s=2.0, horizon_s=0.5, checked_requests=16,
        prompt_len={"median": 24, "sigma": 1.0, "min": 4, "max": 96},
        output_len={"median": 32, "sigma": 0.8, "min": 4, "max": 64}),
        serving={"max_batch": 4, "page_size": 16, "max_pages_per_seq": 10,
                 "num_pages": 40, "prefill_len": 96},
        seconds=8.0, num_hidden_layers=8)
    res = serve.run(ctx, impl="xla", control=True)
    assert res.counters["tokens_compared"] > 200
    assert res.checks[0].ok
    assert res.counters["control_token_gap"] > ctx.limits["token_gap"]
