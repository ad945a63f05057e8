"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes a run's inputs from ``--seed``.

Sizes and arrival times are drawn once, from the mix's own ``shape_seed``,
and are the same for every run; the run's seed draws the token ids (and the
weights).  So every run asks for the same work in the same order, and runs
differ by the system's noise, not by how much work a seed happened to ask
for.  Token ids change no size: the engine decodes greedily to each
request's budget.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Iterator, List

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


def load_mix(name: str) -> dict:
    with open(os.path.join(TRAFFIC_DIR, name + ".json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` lognormal lengths with the spec's median and sigma, clipped to
    [min, max] and rounded to whole tokens."""
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


# ---------------------------------------------------------------- training
def train_layouts(mix: dict) -> List[List[List[int]]]:
    """The mix's step layouts: for each step, for each row, the document
    lengths packed into it in order, the last one truncated to fill the row
    exactly (no padding)."""
    rng = np.random.default_rng(mix["shape_seed"])
    steps = []
    for _ in range(mix["layouts"]):
        rows = []
        for _ in range(mix["rows"]):
            row, used = [], 0
            while used < mix["row_len"]:
                n = int(lengths(rng, mix["doc_len"], 1)[0])
                n = min(n, mix["row_len"] - used)
                row.append(n)
                used += n
            rows.append(row)
        steps.append(rows)
    return steps


@dataclasses.dataclass
class TrainStep:
    rows: List[List[int]]          # document lengths of each row, in order
    batch: dict                    # host numpy arrays, the program's format


def train_steps(mix: dict, seed: int, vocab: int) -> Iterator[TrainStep]:
    """Endless steps: the mix's layouts in turn, token ids drawn from the
    seed, uniform over ``vocab``."""
    layouts = train_layouts(mix)
    rng = np.random.default_rng(seed)
    while True:
        for rows in layouts:
            yield TrainStep(rows=rows, batch=pack_batch(rows, rng, vocab,
                                                        mix["row_len"]))


def pack_batch(rows, rng: np.random.Generator, vocab: int, row_len: int):
    """tokens/labels, segment ids and per-document positions of packed rows
    (the layout ``repro.data.synthetic`` gives packed batches)."""
    b = len(rows)
    seg = np.zeros((b, row_len), np.int32)
    pos = np.zeros((b, row_len), np.int32)
    for i, row in enumerate(rows):
        t = 0
        for j, n in enumerate(row):
            seg[i, t:t + n] = j
            pos[i, t:t + n] = np.arange(n)
            t += n
    tokens = rng.integers(0, vocab, size=(b, row_len), dtype=np.int32)
    return {"tokens": tokens, "labels": tokens, "segment_ids": seg,
            "positions": pos}


# ----------------------------------------------------------------- serving
@dataclasses.dataclass
class ServeRequest:
    rid: int
    due_s: float                   # offset from the start of the traffic
    prompt: np.ndarray             # int32 token ids
    max_new: int


def serve_requests(mix: dict, seed: int, vocab: int) -> List[ServeRequest]:
    """Open-loop Poisson arrivals at the mix's fixed rate, over the warm-up
    and the longest window a run may have (``horizon_s``).  The gaps are
    rescaled so that their mean is exactly 1/rate; the seed draws the
    prompts' token ids."""
    rate = mix["rate_per_s"]
    n = int(math.ceil(rate * (mix["warmup_s"] + mix["horizon_s"])))
    shape = np.random.default_rng(mix["shape_seed"])
    gaps = shape.exponential(1.0 / rate, size=n)
    gaps *= n / rate / gaps.sum()
    prompts = lengths(shape, mix["prompt_len"], n)
    outputs = lengths(shape, mix["output_len"], n)
    rng = np.random.default_rng(seed)
    due = np.cumsum(gaps) - gaps[0]
    return [ServeRequest(rid=i, due_s=float(due[i]),
                         prompt=rng.integers(0, vocab, size=int(prompts[i]),
                                             dtype=np.int32),
                         max_new=int(outputs[i]))
            for i in range(n)]
