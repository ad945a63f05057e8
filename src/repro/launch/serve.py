"""Serving launcher: batched prefill + autoregressive decode.

CPU-runnable smoke examples:
  PYTHONPATH=src python -m repro.launch.serve --arch granite_3_2b --smoke \\
      --batch 4 --prompt-len 64 --gen 32

Paged continuous batching (block-table cache, ragged synthetic requests):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --smoke \\
      --paged --requests 8 --page-size 16 --gen 32

Lazy admission (prompt-only page reservation, one-page decode growth,
youngest-row preemption + re-prefill when the pool runs dry — higher page
utilization than the default eager full-budget reservation):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --smoke \\
      --paged --lazy --requests 8 --gen 32

Prefix caching + chunked prefill (every synthetic request opens with a
common system prompt; matched page-aligned blocks alias already-prefilled
pages and skip their prefill compute, --prefill-chunk interleaves long
prompts with decode steps):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --smoke \\
      --paged --share-prefix --prefill-chunk 32 --requests 8 --gen 32

Speculative decoding (prompt-lookup drafts verified k+1 tokens at a time;
repetitive synthetic prompts make the n-gram drafter actually land):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --smoke \\
      --paged --speculate 4 --requests 8 --gen 32

Tracing the serving loop (a profiler trace under DIR, with the engine's
repro.* host spans; see README "Tracing"):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --smoke \\
      --paged --requests 8 --gen 32 --trace-dir DIR

Distributed paged serving (page pool sharded over the mesh's model axis;
needs that many devices, e.g. XLA_FLAGS=--xla_force_host_platform_device_count=2):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --smoke \\
      --paged --mesh 2 --requests 8 --gen 32

Models are served in their config's dtype (bf16 for every published config),
with the params initialised from ``--seed`` directly into the serve rules'
shardings.  ``--impl`` defaults to the platform's: the Pallas kernels on a
TPU, plain XLA elsewhere.  The paged launcher exits non-zero when a request
ends FAILED outside a ``--fault-plan`` chaos run.
"""

from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.attention import IMPLS, default_impl
from repro.launch.train import parse_mesh
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.spans import trace_gc, untrace_gc
from repro.runtime.steps import init_serve_params, make_serve_steps


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mesh", default="")
    ap.add_argument("--impl", default=None, choices=IMPLS,
                    help="attention impl (default: pallas on a TPU, else xla)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="paged-KV continuous batching (ragged requests)")
    ap.add_argument("--requests", type=int, default=8,
                    help="--paged: synthetic requests to serve")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="--paged: concurrent decode slots")
    ap.add_argument("--lazy", action="store_true",
                    help="--paged: lazy page growth + preemption/re-prefill "
                         "instead of eager full-budget reservation")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="--paged: override the page-pool size (0 = auto; "
                         "shrink it to watch --lazy preempt)")
    ap.add_argument("--share-prefix", action="store_true",
                    help="--paged: content-addressed prefix caching + "
                         "copy-on-write pages (requests then share a common "
                         "system prompt so the cache has something to hit)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="--paged: max prompt tokens prefilled per engine "
                         "iteration (0 = whole prompts at once)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="--paged: speculative decoding — verify up to this "
                         "many prompt-lookup draft tokens per decode step "
                         "(0 = off); token-identical to plain greedy decode")
    ap.add_argument("--num-splits", type=int, default=0,
                    help="split-KV decode: parallel KV partitions per "
                         "(batch, kv-head) row (0 = 1, or autotuned with "
                         "--autotune)")
    ap.add_argument("--deadline-ms", type=float, default=0,
                    help="--paged: per-request wall-clock deadline in "
                         "milliseconds; expired requests terminate with a "
                         "TIMEOUT outcome (0 = no deadline)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="--paged: bounded admission queue — submissions "
                         "past this many waiting requests shed with a SHED "
                         "outcome (0 = unbounded)")
    ap.add_argument("--fault-plan", type=int, default=-1,
                    help="--paged: seed a replayable chaos FaultPlan "
                         "(serving/faults.py) injecting pool exhaustion, "
                         "preemption storms, freed-page poison, NaN logits "
                         "and cancellations (-1 = off)")
    ap.add_argument("--autotune", action="store_true",
                    help="pick --num-splits from the perf/autotune.py cost "
                         "model (persistent cache; explicit --num-splits "
                         "wins)")
    ap.add_argument("--trace-dir", default="",
                    help="--paged: write a profiler trace of the serving "
                         "loop under this directory (.xplane.pb: the "
                         "device's operations beside the engine's repro.* "
                         "host spans and Python GC)")
    args = ap.parse_args(argv)
    args.impl = args.impl or default_impl()
    enable_compile_cache()

    cfg = (configs.smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode step")
    mesh = parse_mesh(args.mesh)

    if args.paged:
        return serve_paged(cfg, args, mesh)

    max_len = args.prompt_len + args.gen
    num_splits, block_kv = args.num_splits or 1, 128   # Ctx.block_kv default
    if args.autotune and not args.num_splits:
        from repro.perf.autotune import DecodeShape, plan_decode_persistent
        shape = DecodeShape(batch=args.batch, hkv=cfg.num_kv_heads,
                            group=cfg.num_heads // cfg.num_kv_heads,
                            kv_len=max_len, head_dim=cfg.head_dim,
                            dtype_bytes=jnp.dtype(cfg.dtype).itemsize)
        plan = plan_decode_persistent(shape)
        num_splits, block_kv = plan.num_splits, plan.block_kv
        print(f"autotune: num_splits={plan.num_splits} "
              f"block_kv={plan.block_kv} ({plan.source}, "
              f"predicted {plan.time_s*1e6:.1f}us/layer)")
    arts = make_serve_steps(cfg, mesh=mesh, impl=args.impl, max_len=max_len,
                            batch=args.batch, num_splits=num_splits,
                            block_kv=block_kv,
                            xla_chunk=min(1024, args.prompt_len))

    key = jax.random.PRNGKey(args.seed)
    params = init_serve_params(cfg, key, mesh)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    caches = arts.cache_init_fn()
    t0 = time.perf_counter()
    logits, caches = arts.prefill_fn(params, prompt, None, caches)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0
    tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
    out_tokens = [np.asarray(tok)]
    t0 = time.perf_counter()
    for i in range(args.gen - 1):
        logits, caches = arts.decode_fn(params, tok, caches,
                                        jnp.int32(args.prompt_len + i))
        tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
        out_tokens.append(np.asarray(tok))
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0
    gen = np.stack(out_tokens, axis=1)
    print(f"prefill: {args.batch}x{args.prompt_len} in {t_prefill*1e3:.1f}ms; "
          f"decode: {args.gen-1} steps in {t_decode*1e3:.1f}ms "
          f"({(args.gen-1)*args.batch/max(t_decode,1e-9):.1f} tok/s)")
    print("generated (first row):", gen[0][:16])


def serve_paged(cfg, args, mesh=None):
    """Continuous batching over ragged synthetic requests (paged KV cache)."""
    from repro.serving import (FaultPlan, Outcome, PagedCacheConfig,
                               ServingEngine)

    key = jax.random.PRNGKey(args.seed)
    n_shards = int(mesh.shape.get("model", 1)) if mesh is not None else 1
    params = init_serve_params(cfg, key, mesh)
    rs = np.random.RandomState(args.seed)
    budget = args.prompt_len + args.gen
    # pool sized so roughly half the requests fit at once — the scheduler
    # has to actually evict/admit, which is the scenario being demoed —
    # then padded so the page-aligned shard split divides evenly
    num_pages = args.num_pages or (n_shards + max(2, args.requests // 2) * (
        -(-budget // args.page_size) + 1))
    num_pages = -(-num_pages // n_shards) * n_shards
    pcfg = PagedCacheConfig(
        page_size=args.page_size,
        max_batch=args.max_batch,
        max_pages_per_seq=-(-budget // args.page_size) + 1,
        num_pages=num_pages,
        num_shards=n_shards)
    # lazy mode: a preempted row re-prefills prompt+generated, so the prefill
    # row must hold a full budget
    prefill_len = max(args.prompt_len, args.page_size)
    if args.lazy:
        prefill_len = max(prefill_len, budget)
    plan = (FaultPlan(seed=args.fault_plan)
            if args.fault_plan >= 0 else None)
    eng = ServingEngine(cfg, pcfg, params, impl=args.impl, mesh=mesh,
                        prefill_len=prefill_len, lazy=args.lazy,
                        num_splits=args.num_splits or None,
                        autotune=args.autotune,
                        share_prefix=args.share_prefix,
                        prefill_chunk=args.prefill_chunk or None,
                        speculate_k=args.speculate or None,
                        deadline_ms=args.deadline_ms or None,
                        max_queue=args.max_queue or None,
                        fault_plan=plan)
    if plan is not None:
        print(f"fault plan (seed {plan.seed}): "
              + " ".join(f"{e.kind}@{e.step}" for e in plan.events))
    if args.autotune or args.num_splits:
        print(f"decode num_splits: {eng.num_splits}"
              + (" (autotuned)" if args.autotune and not args.num_splits
                 else ""))
    # with sharing on, every request opens with one common system prompt
    # (half the nominal prompt length) so the prefix cache has repeats to hit
    system = (rs.randint(0, cfg.vocab_size, size=max(1, args.prompt_len // 2))
              if args.share_prefix else np.zeros(0, np.int64))
    reqs = []
    for _ in range(args.requests):  # ragged: 25%..100% of the nominal lengths
        plen = int(rs.randint(max(1, args.prompt_len // 4), args.prompt_len + 1))
        gen = int(rs.randint(max(1, args.gen // 4), args.gen + 1))
        if args.speculate:
            # a tiled motif gives the prompt-lookup drafter n-gram repeats
            # to match against (uniform-random prompts rarely draft at all)
            motif = rs.randint(0, cfg.vocab_size, size=8)
            tail = np.tile(motif, -(-plen // 8))[:plen]
        else:
            tail = rs.randint(0, cfg.vocab_size, size=plen)
        reqs.append((np.concatenate([system, tail])[:pcfg.max_seq_len
                                                    - args.gen - 1], gen))
    with contextlib.ExitStack() as tracing:
        if args.trace_dir:
            trace_gc()
            tracing.callback(untrace_gc)
            tracing.enter_context(jax.profiler.trace(args.trace_dir))
        out, stats = eng.run(reqs)
    mode = "lazy" if args.lazy else "eager"
    print(f"served {len(out)} requests ({stats['generated_tokens']:.0f} tokens) "
          f"in {stats['wall_s']*1e3:.1f}ms: {stats['tokens_per_s']:.1f} tok/s, "
          f"{stats['decode_steps']:.0f} decode steps, "
          f"{mode} page utilization {stats['mean_utilization']:.1%}")
    print(f"scheduler: {stats['preemptions']:.0f} preemptions, "
          f"{stats['pages_grown']:.0f} pages grown lazily, "
          f"{stats['pages_reclaimed']:.0f} out-of-window pages reclaimed")
    if args.share_prefix or args.prefill_chunk:
        print(f"prefix/chunking: {stats['prefill_tokens']:.0f} prompt tokens "
              f"prefilled, {stats['prefill_tokens_skipped']:.0f} skipped via "
              f"prefix hits, {stats['pages_shared']:.0f} page aliases, "
              f"{stats['cow_copies']:.0f} copy-on-writes")
    if args.speculate:
        print(f"speculation: {stats['drafted_tokens']:.0f} tokens drafted, "
              f"{stats['accepted_tokens']:.0f} accepted "
              f"({stats['acceptance_rate']:.1%}), "
              f"{stats['generated_tokens'] / max(stats['decode_steps'], 1):.2f} "
              f"tokens/verify step")
    counts = stats["outcomes"]
    print("outcomes: " + " ".join(f"{k}={v}" for k, v in counts.items())
          + (f" (watchdog_fires={stats['watchdog_fires']:.0f})"
             if stats["watchdog_fires"] else ""))
    if 0 in out:
        print("generated (request 0):", out[0][:16])
    else:  # request 0 cancelled/timed out/shed/failed under a fault plan
        print("request 0 did not complete:",
              eng.results[0].outcome.value, "—", eng.results[0].reason)
    failed = [r for r in eng.results.values() if r.outcome is Outcome.FAILED]
    if failed and plan is None:
        raise SystemExit(f"{len(failed)} request(s) FAILED, e.g. request "
                         f"{failed[0].rid}: {failed[0].reason}")
    return eng


if __name__ == "__main__":
    main()
