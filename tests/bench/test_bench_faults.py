"""A whole run of each cell's kind, past the harness's look for a chip, at a
tiny size on the CPU: ``correct`` holds for the program as it is and comes
out false with the timed path broken underneath, once for each fault the
cell can have."""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import gen, harness  # noqa: E402
from bench import run as bench_run  # noqa: E402

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
            vocab_size=251)
TINY_MIX = {
    # enough tokens that bf16 rounding averages out of the loss as it does
    # at the cells' size
    "train": dict(row_len=1024, layouts=4,
                  doc_len={"median": 128, "sigma": 1.0, "min": 8,
                           "max": 1024}),
    "serve": dict(rate_per_s=4.0, warmup_s=1.0, horizon_s=3.0,
                  prompt_len={"median": 24, "sigma": 1.0, "min": 4,
                              "max": 96},
                  output_len={"median": 12, "sigma": 0.8, "min": 4,
                              "max": 32},
                  checked_requests=3),
}
TINY_SERVING = {"max_batch": 4, "page_size": 16, "max_pages_per_seq": 9,
                "num_pages": 40, "prefill_len": 96}
M = harness.load_manifest()
KIND = {w["name"]: gen.load_mix(w["traffic"])["kind"] for w in M["workloads"]}


def stale_state(step_fn):
    """A step that hands back its state unchanged (and still reports a
    loss): it runs on copies of its donated inputs."""
    def step(params, state, batch, i):
        copy = jax.tree.map(jnp.copy, (params, state))
        return params, state, step_fn(*copy, batch, i)[2]
    return step


def half_batch(step_fn):
    """A step that leaves out the second half of every row, the loss the
    mean over the rest."""
    def step(params, state, batch, i):
        seg = batch["segment_ids"]
        cut = jnp.arange(seg.shape[1]) >= seg.shape[1] // 2
        return step_fn(params, state, dict(
            batch, segment_ids=jnp.where(cut, -1, seg)), i)
    return step


def altered_token(decode_fn):
    """A decode step whose logits are shifted by one token, so every
    decoded token is altered where it is produced."""
    def decode(*args):
        logits, caches = decode_fn(*args)
        return jnp.roll(logits, 1, axis=-1), caches
    return decode


FAULTS = {"train": [stale_state, half_batch], "serve": [altered_token]}
CASES = [(cell, fault) for cell, kind in KIND.items()
         for fault in [None] + FAULTS[kind]]


def plant(monkeypatch, kind, fault):
    """Break the timed path underneath the harness: wrap the jitted step
    that the program's step builder hands the driver."""
    from repro.runtime import steps
    if kind == "train":
        real = steps.make_train_step

        def make_train_step(*a, **k):
            arts = real(*a, **k)
            arts.step_fn = fault(arts.step_fn)
            return arts
        monkeypatch.setattr(steps, "make_train_step", make_train_step)
    else:
        real = steps.make_serve_steps

        def make_serve_steps(*a, **k):
            arts = real(*a, **k)
            arts.decode_fn = fault(arts.decode_fn)
            return arts
        from repro.serving import engine
        monkeypatch.setattr(engine, "make_serve_steps", make_serve_steps)


@pytest.fixture
def tiny_cells(monkeypatch):
    real_config, real_mix = harness.load_config, gen.load_mix

    def load_config(manifest, name):
        c = dict(real_config(manifest, name), **TINY)
        if "serving" in c:
            c["serving"] = TINY_SERVING
        return c

    def load_mix(name):
        m = real_mix(name)
        m.update(TINY_MIX[m["kind"]])
        if m["kind"] == "train":
            m["rows"] = min(m["rows"], 2)
        return m

    monkeypatch.setattr(harness, "load_config", load_config)
    monkeypatch.setattr(gen, "load_mix", load_mix)


@pytest.mark.parametrize("cell,fault", CASES, ids=lambda x: getattr(
    x, "__name__", x))
def test_correct_only_when_the_timed_path_is_sound(tiny_cells, monkeypatch,
                                                   cell, fault):
    if fault is not None:
        plant(monkeypatch, KIND[cell], fault)
    args = argparse.Namespace(workload=cell, seed=2 ** 31 + 7, seconds=1.0,
                              trace=0)
    line, checks = bench_run.run_cell(args, M, jax.devices(),
                                      time.perf_counter(),
                                      driver_kwargs={"impl": "xla"})
    assert line["correct"] is (fault is None), line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == set(harness.load_limits(cell))
    assert line["attempted"] >= 1 and line["failed"] == 0
    names = {m["name"] for m in harness.cell_metrics(M, cell, "end_to_end")}
    assert set(line["metrics"]) == names
    json.dumps(line)
