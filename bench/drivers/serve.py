"""Serving cells: the program's paged engine under open-loop arrivals.

Set-up builds the engine around weights the benchmark makes from the seed,
compiles its steps with one short request, and then serves the requests due
in the mix's warm-up, so that the window starts with the engine occupied as
it stays.  The window drives ``ServingEngine.submit`` and the engine's
one-iteration method, submitting each request when it is due.  A token counts
as delivered when the iteration that produced it returns.

Once the window has closed, a sample of the finished requests, drawn from
the seed and holding the one with the most served tokens, is compared with
the plain reference: the widest gap by which a served token's reference
logit lies below the reference's best logit at that position.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from bench import gen, weights
from bench.harness import (Check, RunContext, RunResult, peak_bytes,
                           start_trace, program_config, span)

WARM_RID = 1 << 40


class Recorder:
    """Wraps the engine's jitted step callables: a host span around each
    call, and what each call was given, for the per-layer metrics."""

    def __init__(self, engine):
        self.on = False
        self.prefill_segments = []     # per prefill call: device seg ids
        self.decode_kv_len = []        # per decode call: device kv_len
        pre, dec = engine.prefill_fn, engine.decode_fn

        def prefill(params, tokens, seg, *rest):
            with span("prefill"):
                out = pre(params, tokens, seg, *rest)
            if self.on:
                self.prefill_segments.append(seg)
            return out

        def decode(params, token, caches, tables, kv_len):
            with span("decode"):
                logits, caches = dec(params, token, caches, tables, kv_len)
            if self.on:
                self.decode_kv_len.append(kv_len)
            return logits, caches

        engine.prefill_fn = prefill
        engine.decode_fn = decode


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else None


def run(ctx: RunContext, impl=None, control: bool = False) -> RunResult:
    import jax
    from repro.core.attention import default_impl
    from repro.serving import Outcome, PagedCacheConfig, ServingEngine

    c, mix, sv = ctx.config, ctx.mix, ctx.config["serving"]
    pcfg = PagedCacheConfig(page_size=sv["page_size"],
                            num_pages=sv["num_pages"],
                            max_batch=sv["max_batch"],
                            max_pages_per_seq=sv["max_pages_per_seq"])
    with span("init"):
        params = weights.make_params(c, ctx.seed)
        engine = ServingEngine(program_config(c), pcfg, params,
                               impl=impl or default_impl(),
                               prefill_len=sv["prefill_len"])
    rec = Recorder(engine)
    sched = engine.scheduler

    # -- compile every step the traffic uses: one short request
    engine.submit(np.arange(16, dtype=np.int32), 2, rid=WARM_RID)
    while not sched.idle:
        engine._iteration()
        engine._iter += 1

    requests = gen.serve_requests(mix, ctx.seed, c["vocab_size"])
    due = {r.rid: r.due_s for r in requests}
    seen = {}                       # rid → tokens delivered so far
    delivered = {}                  # rid → delivery time of each token
    admitted = {}                   # rid → start of the admitting iteration
    occupancy = []                  # (seconds since t0, active, waiting)
    nxt = [0]
    t0 = time.perf_counter()

    def deliver(t):
        for seq in sched.active.values():
            rid = seq.request.rid
            n = len(seq.all_generated)
            if n > seen.get(rid, 0):
                delivered.setdefault(rid, []).extend(
                    [t] * (n - seen.get(rid, 0)))
                seen[rid] = n
        for rid, res in engine.results.items():
            n = len(res.tokens)
            if rid in due and n > seen.get(rid, 0):
                delivered.setdefault(rid, []).extend(
                    [t] * (n - seen.get(rid, 0)))
                seen[rid] = n

    def serve_until(t_end):
        while True:
            now = time.perf_counter()
            while nxt[0] < len(requests) and \
                    t0 + requests[nxt[0]].due_s <= now:
                r = requests[nxt[0]]
                engine.submit(r.prompt, r.max_new, rid=r.rid)
                nxt[0] += 1
            if now >= t_end:
                return
            if sched.idle:
                wake = t_end if nxt[0] >= len(requests) else min(
                    t_end, t0 + requests[nxt[0]].due_s)
                with span("wait_arrival"):
                    time.sleep(max(0.0, wake - now))
                continue
            with span("iteration"):
                engine._iteration()
                engine._iter += 1
            t = time.perf_counter()
            for seq in sched.active.values():
                admitted.setdefault(seq.request.rid, now)
            occupancy.append((t - t0, len(sched.active), len(sched.waiting)))
            deliver(t)

    serve_until(t0 + mix["warmup_s"])
    if ctx.trace:
        start_trace(ctx.trace_dir)
    ws = time.perf_counter()
    setup_s = ws - ctx.t_start
    we = ws + ctx.seconds
    rec.on = True
    ctx.compiles.armed = True
    with span("window"):
        serve_until(we)
    ctx.compiles.armed = False
    rec.on = False
    if ctx.trace:
        jax.profiler.stop_trace()
    peak = peak_bytes(ctx.devices)

    # -- end-to-end metrics over the window
    in_win = [r for r in requests if ws <= t0 + r.due_s < we]
    ttft, queue = [], []
    for r in in_win:
        d = t0 + r.due_s
        times = delivered.get(r.rid, [])
        ttft.append((times[0] if times and times[0] <= we else we) - d)
        queue.append(min(admitted.get(r.rid, we), we) - d)
    gaps, n_out = [], 0
    for rid, times in delivered.items():
        for i, t in enumerate(times):
            if ws <= t <= we:
                n_out += 1
                if i:
                    gaps.append(t - times[i - 1])
    bad = (Outcome.FAILED, Outcome.SHED, Outcome.TIMEOUT)
    failed = sum(1 for r in in_win if r.rid in engine.results
                 and engine.results[r.rid].outcome in bad)
    finished = {rid: np.asarray(res.tokens, np.int32)
                for rid, res in engine.results.items()
                if rid in due and res.outcome is Outcome.COMPLETED}
    counters = {
        "prefill_segments": [np.asarray(s) for s in rec.prefill_segments],
        "decode_kv_len": [np.asarray(k) for k in rec.decode_kv_len],
        "queue_waits_s": queue,
        "compiles_in_window": ctx.compiles.count,
        "samples": {"ttft": len(ttft), "itl": len(gaps)},
        "occupancy": occupancy, "window_start_s": ws - t0,
    }

    # -- free the engine, then compare a sample with the reference
    del engine, rec
    gc.collect()
    check, ctl_gap, n_tok = token_gap_check(ctx, params, requests, finished,
                                           control)
    counters["tokens_compared"] = n_tok
    if control:
        counters["control_token_gap"] = ctl_gap
    return RunResult(
        attempted=len(in_win), failed=failed,
        end_to_end={"setup_s": setup_s,
                    "ttft_p95_ms": 1e3 * _percentile(ttft, 95)
                    if ttft else None,
                    "itl_p95_ms": 1e3 * _percentile(gaps, 95)
                    if gaps else None,
                    "output_tokens_per_s": n_out / ctx.seconds},
        checks=[check], counters=counters, memory_peak_bytes=peak,
        window_s=ctx.seconds)


def sample(requests, finished, seed: int, k: int):
    """``k`` finished requests drawn from the seed, the one with the most
    served tokens among them."""
    rids = sorted(finished)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(finished[r]), -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng(seed)
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False)) \
        if rest else []
    return [longest] + [int(r) for r in pick]


def token_gap_check(ctx: RunContext, params, requests, finished,
                    control: bool = False):
    """The widest gap, over the sampled requests' served tokens, between the
    reference's best logit and its logit of the served token.  Returns (the
    check, the control's widest gap or 0, the tokens compared); the control
    (``bench/control.py`` only) is the token the float8 reference ranks
    first at each of those positions."""
    from bench import reference
    length = ctx.config["serving"]["prefill_len"] \
        + ctx.mix["output_len"]["max"]
    stats = reference.make_serve_stats(ctx.config, length, control)
    by_rid = {r.rid: r for r in requests}
    worst, worst_ctl, n_tok = 0.0, 0.0, 0
    picked = sample(requests, finished, ctx.seed, ctx.mix["checked_requests"])
    for rid in picked:
        prompt, served = by_rid[rid].prompt, finished[rid]
        seq = np.concatenate([prompt, served]).astype(np.int32)
        row = np.zeros((length,), np.int32)
        row[:len(seq)] = seq
        out = stats(params, row)
        lo, hi = len(prompt) - 1, len(seq) - 1
        gap = out[0][lo:hi] - out[1][lo:hi]
        worst = max(worst, float(np.max(gap)) if np.all(np.isfinite(gap))
                    else math.inf)
        if control:
            worst_ctl = max(worst_ctl, float(np.max(out[0][lo:hi]
                                                    - out[2][lo:hi])))
        n_tok += hi - lo
    check = Check("token_gap", worst if picked else math.inf,
                  ctx.limits["token_gap"])
    return check, worst_ctl, n_tok
