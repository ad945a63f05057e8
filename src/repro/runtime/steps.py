"""jit-compiled train / prefill / decode steps, mesh-aware.

``make_train_step`` / ``make_serve_steps`` return jitted callables with
in/out shardings derived from the sharding-rule engine; with ``mesh=None``
they degrade to single-device functions (smoke tests, examples).

These builders are the single source for the launcher, the dry-run, the
benchmarks and the distributed tests — what the dry-run compiles is exactly
what the trainer runs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import (ShardingRules, default_rules,
                                        vocab_pad_for)
from repro.models import lm
from repro.models.layers import Ctx
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.optim.schedule import cosine_schedule


def _make_ctx(cfg, rules: Optional[ShardingRules], impl: str, seed,
              deterministic: bool, decode: bool = False,
              xla_chunk: int = 1024, xla_unroll: bool = False,
              decode_write: str = "dus",
              num_splits: int = 1, block_kv: int = 128) -> Ctx:
    return Ctx(rules=rules, impl=impl, deterministic=deterministic, seed=seed,
               decode=decode, xla_chunk=xla_chunk, xla_unroll=xla_unroll,
               decode_write=decode_write, num_splits=num_splits,
               block_kv=block_kv)


@dataclasses.dataclass
class TrainArtifacts:
    step_fn: Any            # (params, opt_state, batch, step) → (p, o, metrics)
    init_fn: Any            # key → (params, opt_state)
    shardings: Any          # dict: params/opt_state/batch NamedShardings
    rules: Optional[ShardingRules]


def make_train_step(cfg, *, mesh=None, opt: AdamWConfig = AdamWConfig(),
                    impl: str = "xla", total_steps: int = 10000,
                    warmup_steps: int = 100, microbatch: Optional[int] = None,
                    aux_weight: float = 0.01, xla_chunk: int = 1024,
                    xla_unroll: bool = False,
                    donate: bool = True) -> TrainArtifacts:
    rules = default_rules(mesh, cfg) if mesh is not None else None
    vocab_pad = vocab_pad_for(mesh) if mesh is not None else 1

    def init_fn(key):
        params, specs = lm.init_params(cfg, key, vocab_pad_to=vocab_pad)
        opt_state = adamw_init(params, opt)
        return params, opt_state, specs

    def loss_of(params, batch, seed):
        ctx = _make_ctx(cfg, rules, impl, seed,
                        deterministic=(cfg.dropout_rate == 0.0),
                        xla_chunk=xla_chunk, xla_unroll=xla_unroll)
        return lm.loss_fn(cfg, params, batch, ctx, aux_weight=aux_weight)

    def train_step(params, opt_state, batch, step):
        seed = (step.astype(jnp.uint32) * jnp.uint32(2654435761)
                ).astype(jnp.int32)  # per-step dropout stream
        if microbatch is None:
            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, batch, seed)
        else:
            # gradient accumulation over microbatches (PP-style scheduling
            # substrate): scan over batch splits, mean the grads.
            n_micro = batch["labels"].shape[0] // microbatch

            def split(x):
                return x.reshape((n_micro, microbatch) + x.shape[1:])

            micro = jax.tree.map(split, batch)

            def acc(carry, mb):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(loss_of, has_aux=True)(
                    params, mb, seed)
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), m

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (g_sum, l_sum), ms = jax.lax.scan(acc, (g0, 0.0), micro)
            grads = jax.tree.map(lambda g: g / n_micro, g_sum)
            loss = l_sum / n_micro
            metrics = jax.tree.map(lambda x: x[-1], ms)
        lr = cosine_schedule(step, warmup_steps, total_steps, opt.lr)
        params, opt_state, om = adamw_update(grads, opt_state, params, opt,
                                             lr=lr)
        metrics = dict(metrics, **om, lr=lr, loss=loss)
        return params, opt_state, metrics

    shardings = None
    if mesh is not None:
        params_shape, specs = lm.abstract_params(cfg, vocab_pad_to=vocab_pad)
        p_shard = rules.tree_shardings(params_shape, specs)
        o_shard = _opt_shardings(p_shard, opt)
        b_shard = {
            "tokens": rules.sharding_for(("batch", None), None),
            "labels": rules.sharding_for(("batch", None), None),
            "embeds": rules.sharding_for(("batch", None, None), None),
            # packed (varlen) batches ride along with the same batch sharding
            "segment_ids": rules.sharding_for(("batch", None), None),
            "positions": rules.sharding_for(("batch", None), None),
        }
        repl = NamedSharding(mesh, P())
        step_fn = jax.jit(
            train_step,
            in_shardings=(p_shard, o_shard, None, repl),
            out_shardings=(p_shard, o_shard, repl),
            donate_argnums=(0, 1) if donate else ())
        shardings = {"params": p_shard, "opt": o_shard, "batch": b_shard}
    else:
        step_fn = jax.jit(train_step, donate_argnums=(0, 1) if donate else ())

    return TrainArtifacts(step_fn=step_fn, init_fn=init_fn,
                          shardings=shardings, rules=rules)


def _opt_shardings(p_shard, opt: AdamWConfig):
    from repro.optim.adamw import AdamWState
    none_spec = None
    return AdamWState(
        step=NamedSharding(list(jax.tree.leaves(p_shard))[0].mesh, P()),
        m=p_shard, v=p_shard,
        master=p_shard if opt.keep_master else None)


def serve_param_shardings(cfg, mesh, params):
    """The serve rules' NamedSharding pytree for ``params`` (arrays or
    shapes; the logical specs are structural, so any vocab padding works)."""
    _, specs = lm.abstract_params(cfg, vocab_pad_to=1)
    return default_rules(mesh, cfg, serve=True).tree_shardings(params, specs)


def init_serve_params(cfg, key, mesh=None):
    """Random params for serving, built where they live.

    With a mesh the init is one jitted program whose ``out_shardings`` are
    the serve rules, so every device computes only its own shards: a model
    larger than one device's memory never has to fit on one device first.
    The vocabulary is padded to the mesh's model axis.
    """
    if mesh is None:
        return lm.init_params(cfg, key)[0]
    pad = vocab_pad_for(mesh)
    shapes, _ = lm.abstract_params(cfg, vocab_pad_to=pad)
    init = jax.jit(lambda k: lm.init_params(cfg, k, vocab_pad_to=pad)[0],
                   out_shardings=serve_param_shardings(cfg, mesh, shapes))
    return init(key)


@dataclasses.dataclass
class ServeArtifacts:
    prefill_fn: Any
    decode_fn: Any
    cache_init_fn: Any
    rules: Optional[ShardingRules]          # prefill/param rules
    rules_decode: Optional[ShardingRules] = None
    chunk_prefill_fn: Any = None            # paged only: chunked/suffix prefill
    verify_fn: Any = None                   # paged only: speculative verify-k


def make_serve_steps(cfg, *, mesh=None, impl: str = "xla", max_len: int = 2048,
                     batch: int = 1, xla_chunk: int = 1024,
                     xla_unroll: bool = False,
                     decode_write: str = "dus",
                     num_splits: int = 1, block_kv: int = 128,
                     paged=None) -> ServeArtifacts:
    """paged: optional serving.PagedCacheConfig — switches the cache to a
    global page pool with block-table decode and segment-aware packed
    prefill (the serving subsystem's jitted steps; see docs/serving.md).
    The paged signatures differ from the contiguous ones:

      prefill_fn(params, tokens, segment_ids, positions, dest, state_slots,
                 caches)
          → (logits [B,S,Vpad], caches)     # packed prompts, B prefill rows
      decode_fn(params, token, caches, block_tables, kv_len)
          → (logits [B,Vpad], caches)       # B = paged.max_batch slots
      chunk_prefill_fn(params, tokens, positions, dest, token_tables,
                       token_kv_len, state_slots, state_local, caches)
          → (logits [B,S,Vpad], caches)     # chunked/suffix prefill spans
                                            # (global positions; per-token
                                            # block-table attention)

    state_slots/state_local [B,S] route hybrid SSM/recurrent archs' fixed
    per-slot state rows (each token's decode slot and within-span offset;
    -1/0 for padding) — attention-only archs pass them too and XLA prunes
    the unused inputs.  Decode derives row liveness from kv_len > 0, so its
    signature is unchanged; the verify step stays attention-only (the
    engine rejects speculation on recurrent archs — state can't roll back).
      verify_fn(params, tokens, positions, dest, token_tables,
                token_kv_len, caches)
          → (logits [B,W,Vpad], caches)     # speculative verify-k: same
                                            # per-token primitive, B =
                                            # max_batch decode rows of
                                            # width W = k+1, decode-path
                                            # sharding rules + num_splits

    num_splits / block_kv: split-KV launch parameters for the decode step
    (static — baked into the jitted step; pick both with perf/autotune.py or
    let ``ServingEngine(autotune=True)`` do it). The paged decode ignores
    ``block_kv`` — its KV block is pinned to the page size.
    """
    if paged is not None:
        # distributed pool: the page dim shards over the mesh's model axis
        # (page-aligned — pages never straddle shards); decode runs per-shard
        # local attention + online-softmax partial merge via the shard_map
        # paths in distributed/paged.py. mesh=None keeps the single-host path.
        rules = rules_dec = None
        if mesh is not None:
            from repro.distributed.paged import pool_shard_count
            n_shards = pool_shard_count(mesh)
            if paged.num_shards != n_shards:
                raise ValueError(
                    f"PagedCacheConfig.num_shards={paged.num_shards} must "
                    f"equal the mesh's model-axis size {n_shards} (the "
                    f"allocator reserves one trash page per pool shard)")
            # the page-aligned split itself is validated by PagedCacheConfig
            rules = default_rules(mesh, cfg, serve=True)
            rules_dec = default_rules(mesh, cfg, serve=True, decode=True)

        def cache_init():
            caches = lm.init_paged_cache(cfg, paged)
            if mesh is not None:
                # pool leaf [(n_super,) Hkv, num_pages, page_size, D]: the
                # page axis is always ndim-3 and shards over the model axis;
                # recurrent-state rows (hybrid archs) are tiny and replicate
                from jax.tree_util import tree_map_with_path

                def put(path, x):
                    pool = getattr(path[-1], "key", None) in ("k_pages",
                                                              "v_pages")
                    spec = (P(*(None,) * (x.ndim - 3), "model", None, None)
                            if pool else P())
                    return jax.device_put(x, NamedSharding(mesh, spec))

                caches = tree_map_with_path(put, caches)
            return caches

        def prefill_fn(params, tokens, segment_ids, positions, dest,
                       state_slots, caches):
            ctx = _make_ctx(cfg, rules, impl, 0, True, xla_chunk=xla_chunk,
                            xla_unroll=xla_unroll)
            return lm.paged_prefill(cfg, params, ctx, tokens, segment_ids,
                                    positions, dest, caches, state_slots)

        def decode_fn(params, token, caches, block_tables, kv_len):
            ctx = _make_ctx(cfg, rules_dec, impl, 0, True, xla_chunk=xla_chunk,
                            decode_write=decode_write,
                            num_splits=num_splits)
            return lm.paged_decode_step(cfg, params, ctx, token, caches,
                                        block_tables, kv_len)

        def chunk_prefill_fn(params, tokens, positions, dest, token_tables,
                             token_kv_len, state_slots, state_local, caches):
            ctx = _make_ctx(cfg, rules, impl, 0, True, xla_chunk=xla_chunk,
                            xla_unroll=xla_unroll)
            return lm.paged_chunk_prefill(cfg, params, ctx, tokens, positions,
                                          dest, token_tables, token_kv_len,
                                          caches, state_slots, state_local)

        def verify_fn(params, tokens, positions, dest, token_tables,
                      token_kv_len, caches):
            # decode-path rules + split-KV launch params: the verify step is
            # the latency-bound step it replaces, just k+1 tokens wide
            ctx = _make_ctx(cfg, rules_dec, impl, 0, True, xla_chunk=xla_chunk,
                            decode_write=decode_write,
                            num_splits=num_splits)
            return lm.paged_verify_step(cfg, params, ctx, tokens, positions,
                                        dest, token_tables, token_kv_len,
                                        caches)

        # all steps donate the page pools (the dominant serving tensors):
        # the caller always threads the returned caches into the next call
        return ServeArtifacts(prefill_fn=jax.jit(prefill_fn,
                                                 donate_argnums=(6,)),
                              decode_fn=jax.jit(decode_fn, donate_argnums=(2,)),
                              chunk_prefill_fn=jax.jit(chunk_prefill_fn,
                                                       donate_argnums=(8,)),
                              verify_fn=jax.jit(verify_fn, donate_argnums=(6,)),
                              cache_init_fn=cache_init, rules=rules,
                              rules_decode=rules_dec)

    # prefill and decode get DIFFERENT activation rules: prefill behaves
    # like a forward train pass (FSDP weight gathers amortise over the whole
    # sequence); decode must avoid per-token weight/cache gathers.
    rules = default_rules(mesh, cfg, serve=True) if mesh is not None else None
    rules_dec = (default_rules(mesh, cfg, serve=True, decode=True)
                 if mesh is not None else None)
    vocab_pad = vocab_pad_for(mesh) if mesh is not None else 1

    def cache_init():
        return lm.init_cache(cfg, batch, max_len)

    def prefill_fn(params, tokens, embeds, caches):
        # positional-only: jit in_shardings forbids kwargs
        ctx = _make_ctx(cfg, rules, impl, 0, True, xla_chunk=xla_chunk,
                        xla_unroll=xla_unroll)
        return lm.prefill(cfg, params, ctx, tokens=tokens, embeds=embeds,
                          caches=caches)

    def decode_fn(params, token, caches, position):
        ctx = _make_ctx(cfg, rules_dec, impl, 0, True, xla_chunk=xla_chunk,
                        decode_write=decode_write, num_splits=num_splits,
                        block_kv=block_kv)
        return lm.decode_step(cfg, params, ctx, token, caches, position)

    if mesh is not None:
        params_shape, specs = lm.abstract_params(cfg, vocab_pad_to=vocab_pad)
        p_shard = rules.tree_shardings(params_shape, specs)
        # the KV cache is donated by BOTH steps: prefill writes the prompt
        # K/V into it and decode updates it in place (halves the serving
        # memory footprint — caches are the dominant serving tensor); every
        # caller threads the returned caches into the next call
        prefill_jit = jax.jit(prefill_fn,
                              in_shardings=(p_shard, None, None, None),
                              donate_argnums=(3,))
        decode_jit = jax.jit(decode_fn, donate_argnums=(2,))
        return ServeArtifacts(prefill_fn=prefill_jit, decode_fn=decode_jit,
                              cache_init_fn=cache_init, rules=rules,
                              rules_decode=rules_dec)
    return ServeArtifacts(prefill_fn=jax.jit(prefill_fn, donate_argnums=(3,)),
                          decode_fn=jax.jit(decode_fn, donate_argnums=(2,)),
                          cache_init_fn=cache_init, rules=rules,
                          rules_decode=rules_dec)
