"""The traffic generator: deterministic per seed, within the stated clips,
and the same sizes and arrivals for every seed."""

import glob
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import gen  # noqa: E402

MIXES = sorted(os.path.basename(p)[:-5]
               for p in glob.glob(os.path.join(ROOT, "bench", "traffic",
                                               "*.json")))
TRAIN = [m for m in MIXES if gen.load_mix(m)["kind"] == "train"]
SERVE = [m for m in MIXES if gen.load_mix(m)["kind"] == "serve"]
BIG_SEED = 2 ** 31 + 12345


def take(it, n):
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("mix", TRAIN)
def test_train_steps_are_deterministic_and_clipped(mix):
    m = gen.load_mix(mix)
    a = take(gen.train_steps(m, BIG_SEED, 1000), 3)
    b = take(gen.train_steps(m, BIG_SEED, 1000), 3)
    c = take(gen.train_steps(m, 7, 1000), 3)
    for x, y, z in zip(a, b, c):
        for k in x.batch:
            np.testing.assert_array_equal(x.batch[k], y.batch[k])
        assert x.rows == z.rows                       # same work every seed
        assert not np.array_equal(x.batch["tokens"], z.batch["tokens"])
        assert x.batch["tokens"].shape == (m["rows"], m["row_len"])
        assert x.batch["tokens"].min() >= 0 and x.batch["tokens"].max() < 1000
    spec = m["doc_len"]
    for rows in gen.train_layouts(m):
        for r in rows:
            assert sum(r) == m["row_len"]             # packed, no padding
            assert all(spec["min"] <= n <= spec["max"] for n in r[:-1])
            assert 1 <= r[-1] <= spec["max"]          # the last one truncated


def test_packed_batch_layout():
    rng = np.random.default_rng(0)
    b = gen.pack_batch([[3, 2], [5]], rng, 50, 5)
    np.testing.assert_array_equal(b["segment_ids"],
                                  [[0, 0, 0, 1, 1], [0, 0, 0, 0, 0]])
    np.testing.assert_array_equal(b["positions"],
                                  [[0, 1, 2, 0, 1], [0, 1, 2, 3, 4]])


@pytest.mark.parametrize("mix", SERVE)
def test_serve_requests_are_deterministic_and_clipped(mix):
    m = gen.load_mix(mix)
    a = gen.serve_requests(m, BIG_SEED, 1000)
    b = gen.serve_requests(m, BIG_SEED, 1000)
    c = gen.serve_requests(m, 3, 1000)
    assert len(a) == len(c) >= m["rate_per_s"] * (m["warmup_s"]
                                                  + m["horizon_s"])
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(x.prompt, y.prompt)
        assert (x.due_s, x.max_new, len(x.prompt)) == \
            (z.due_s, z.max_new, len(z.prompt))
        assert m["prompt_len"]["min"] <= len(x.prompt) <= m["prompt_len"]["max"]
        assert m["output_len"]["min"] <= x.max_new <= m["output_len"]["max"]
        assert x.prompt.min() >= 0 and x.prompt.max() < 1000
    assert any(not np.array_equal(x.prompt, z.prompt) for x, z in zip(a, c))
    due = np.array([r.due_s for r in a])
    assert due[0] == 0 and np.all(np.diff(due) >= 0)
    # Poisson arrivals at the mix's rate: exponential gaps of mean 1/rate
    gaps = np.diff(due)
    assert abs(gaps.mean() * m["rate_per_s"] - 1) < 0.2
