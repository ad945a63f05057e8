"""Training cells: the program's jitted train step on packed batches.

Set-up builds one object, the compiled step with its state, from weights the
benchmark makes from the seed.  Its first ``checked_steps`` steps run through
the same loop as the window and are the ones the plain reference follows;
then the window drives that same object until ``--seconds`` have passed and
the step in flight has finished.  Per step the loop does what the program's
trainer does: build the batch on the host, place it, step, read the loss.
"""

from __future__ import annotations

import gc
import math
import time

from bench import gen, weights
from bench.harness import (Check, RunContext, RunResult, peak_bytes,
                           start_trace, program_config, span,
                           worst_leaf_gap)


def run(ctx: RunContext, impl=None) -> RunResult:
    import jax
    import jax.numpy as jnp
    from repro.core.attention import default_impl
    from repro.optim import AdamWConfig, adamw_init
    from repro.runtime.steps import make_train_step

    from bench import reference

    c, mix = ctx.config, ctx.mix
    o = c["optimizer"]
    opt = AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                      weight_decay=o["weight_decay"],
                      grad_clip=o["grad_clip"])
    arts = make_train_step(program_config(c), opt=opt,
                           impl=impl or default_impl(),
                           total_steps=o["total_steps"],
                           warmup_steps=o["warmup_steps"])
    with span("init"):
        params = weights.make_params(c, ctx.seed)
        state = jax.jit(lambda p: adamw_init(p, opt))(params)
    steps = gen.train_steps(mix, ctx.seed, c["vocab_size"])
    norms = jax.jit(reference.leaf_norms)
    losses = []

    def one_step(i):
        nonlocal params, state
        with span("batch"):
            st = next(steps)
            batch = jax.device_put(st.batch, ctx.devices[0])
        with span("train_step"):
            params, state, m = arts.step_fn(params, state, batch,
                                            jnp.int32(i))
            loss = float(m["loss"])
        losses.append(loss)
        return st.rows, loss

    # -- set-up: the checked steps, which also compile the step
    n_check = mix["checked_steps"]
    grad0 = None
    for i in range(n_check):
        one_step(i)
        if i == 0:
            grad0 = jax.device_get(jax.tree.map(
                lambda x: x / (1 - opt.b1), norms(state.m)))
    p0 = weights.make_params(c, ctx.seed)
    change = jax.device_get(jax.jit(lambda a, b: reference.leaf_norms(
        jax.tree.map(lambda x, y: x - y.astype(jnp.float32), a, b)))(
            state.master, p0))
    del p0
    jax.block_until_ready(params)

    # -- the window
    if ctx.trace:
        start_trace(ctx.trace_dir)
    t_window = time.perf_counter()
    setup_s = t_window - ctx.t_start
    ctx.compiles.armed = True
    win_rows, win_losses = [], []
    with span("window"):
        i = n_check
        while True:
            rows, loss = one_step(i)
            win_rows.append(rows)
            win_losses.append(loss)
            i += 1
            if time.perf_counter() - t_window >= ctx.seconds:
                break
    window_s = time.perf_counter() - t_window
    ctx.compiles.armed = False
    if ctx.trace:
        jax.profiler.stop_trace()
    peak = peak_bytes(ctx.devices)

    # -- free the program's state, then the reference
    del params, state, arts
    gc.collect()
    ref_batches = []
    for st in gen.train_steps(mix, ctx.seed, c["vocab_size"]):
        ref_batches.append(st.batch)
        if len(ref_batches) == n_check:
            break
    ref_losses, ref_grad0, ref_change = reference.train_readings(
        c, reference.Num(), lambda: weights.make_params(c, ctx.seed),
        ref_batches, n_check)
    loss_gap = max(abs(a - b) for a, b in zip(losses[:n_check], ref_losses))
    if not all(math.isfinite(x) for x in losses[:n_check]):
        loss_gap = math.inf
    checks = [
        Check("loss_gap", loss_gap, ctx.limits["loss_gap"]),
        Check("grad_gap", worst_leaf_gap(grad0, ref_grad0, ref_grad0),
              ctx.limits["grad_gap"]),
        Check("update_gap", worst_leaf_gap(change, ref_change, ref_grad0),
              ctx.limits["update_gap"]),
    ]
    tokens = sum(sum(sum(r) for r in rows) for rows in win_rows)
    failed = sum(1 for x in win_losses if not math.isfinite(x))
    return RunResult(
        attempted=len(win_rows), failed=failed,
        end_to_end={"setup_s": setup_s,
                    "train_tokens_per_s": tokens / window_s},
        checks=checks,
        counters={"step_rows": win_rows, "tokens": tokens,
                  "compiles_in_window": ctx.compiles.count,
                  "losses": losses[:n_check], "ref_losses": ref_losses},
        memory_peak_bytes=peak, window_s=window_s)

