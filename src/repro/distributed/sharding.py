"""Logical-axis sharding rules with divisibility fallback.

Params and activations are annotated with *logical* axis names
(models/layers.py); this module maps them onto mesh axes per architecture and
records every fallback it takes, so the dry-run can report exactly how each of
the 10 heterogeneous archs was laid out on the same (pod, data, model) mesh.

Key rules (see docs/architecture.md):
  batch        → (pod, data)  [DP]
  seq          → model        [Megatron-style sequence parallelism between
                               layers; attention/MLP gather internally]
  heads/mlp/vocab/experts/rnn → model  [TP/EP], iff divisible, else replicate
  embed (param dim) → data when cfg.fsdp  [FSDP/ZeRO; gathered per layer]

The full ZeRO-3 profile (``_fsdp_rules``: no TP at all, params and batch
jointly over (data, model)) replaces the rule set above only when the config
*opts in* to parameter sharding with ``fsdp=True`` AND selects
``sharding_profile="fsdp"``.  The profile string alone is an annotation of
what the hillclimb found best at production scale; honoring it without the
``fsdp`` opt-in silently FSDP-shards the embed/vocab axis where TP /
replication is expected, which turns per-layer weight gathers into
whole-table all-gathers (the seed-state bug behind the four
``test_sharding_rules`` xfails and the sharded-vs-single-device drift in
``test_distributed``).

GSPMD cannot partition a Pallas (Mosaic) kernel, so on a mesh the attention
and contiguous-decode kernels run per shard in ``shard_map`` over the same
rules' batch and head axes (:func:`attention_per_shard`,
:func:`decode_per_shard`); the XLA impls stay with GSPMD.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass
class ShardingRules:
    mesh: Mesh
    rules: Dict[str, Any]                 # logical name → mesh axis (or None)
    fallbacks: Dict[str, str] = dataclasses.field(default_factory=dict)

    def spec_for(self, logical_axes: Tuple[Optional[str], ...],
                 shape: Optional[Tuple[int, ...]] = None) -> P:
        """Map logical axes → PartitionSpec, dropping non-divisible entries."""
        parts = []
        for i, name in enumerate(logical_axes):
            axis = self.rules.get(name) if name else None
            if axis is None:
                parts.append(None)
                continue
            size = _axis_size(self.mesh, axis)
            if shape is not None and shape[i] % size != 0:
                self.fallbacks[f"{name}[{shape[i]}]"] = (
                    f"not divisible by {axis}={size} → replicated")
                parts.append(None)
            else:
                parts.append(axis)
        # a mesh axis may appear at most once in a spec
        seen = set()
        clean = []
        for p_ in parts:
            names = p_ if isinstance(p_, tuple) else (p_,)
            if p_ is not None and any(n in seen for n in names):
                clean.append(None)
            else:
                clean.append(p_)
                seen.update(n for n in names if n)
        return P(*clean)

    def sharding_for(self, logical_axes, shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(logical_axes, shape))

    def constrain(self, x, logical_axes):
        """Activation sharding constraint (used as Ctx.constrain)."""
        spec = self.spec_for(tuple(logical_axes), tuple(x.shape))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def tree_shardings(self, params, specs):
        """NamedSharding pytree for a param pytree + logical-spec pytree."""
        return jax.tree.map(
            lambda p, s: self.sharding_for(tuple(s), tuple(p.shape)),
            params, specs, is_leaf=lambda x: isinstance(x, tuple))


def _axis_size(mesh: Mesh, axis) -> int:
    if isinstance(axis, tuple):
        size = 1
        for a in axis:
            size *= mesh.shape[a]
        return size
    return mesh.shape[axis]


def default_rules(mesh: Mesh, cfg, *, serve: bool = False,
                  decode: bool = False) -> ShardingRules:
    """Per-arch logical→mesh mapping.

    The same rule set covers train and serve: non-divisible dims (e.g. a
    decode step's seq=1, or kv_heads=8 on a 16-way model axis) fall back to
    replication automatically, and the spec builder never assigns one mesh
    axis twice — so e.g. the KV cache shards over kv_heads when divisible and
    over cache sequence (distributed flash-decode) otherwise."""
    dp: Any = tuple(a for a in ("pod", "data") if a in mesh.shape) or None
    if dp is not None and len(dp) == 1:
        dp = dp[0]
    tp = "model" if "model" in mesh.shape else None

    if not serve and uses_fsdp_profile(cfg):
        # ZeRO-3 needs BOTH flags: the profile string alone is a scale
        # annotation, not an opt-in (module docstring) — without cfg.fsdp the
        # arch keeps the TP-SP rules below.
        return _fsdp_rules(mesh, cfg)  # train-only profile (see above)

    rules: Dict[str, Any] = {
        # activations
        "batch": dp,
        "seq": tp,            # sequence-parallel residuals between layers
        "seq_full": None,     # inside attention: per-device full seq
        "heads": tp,
        "kv_heads": tp,
        "head_dim": None,
        "moe_groups": dp,
        # params
        "vocab": tp,
        "embed": None,
        "mlp": tp,
        "expert_mlp": None,   # per-expert FFN dim stays local (E is sharded)
        "q_proj": tp,
        "kv_proj": tp,
        "experts": tp,
        "rnn": tp,
        "state": None,
        "layers": None,       # stacked-scan leading dim
        "kv_cache_seq": tp,   # long-KV decode: cache seq sharded when kv_heads
                              # can't be (spec builder enforces axis uniqueness)
    }
    if (cfg is not None and getattr(cfg, "fsdp", False) and not decode
            and "data" in mesh.shape):
        # FSDP: weights gathered per layer inside scan. Train + prefill only
        # (both have whole-sequence compute to overlap the gathers); per-token
        # weight all-gathers would dominate decode (qwen3 decode went
        # 6ms→146ms when FSDP leaked into decode rules — §Perf iteration 3).
        rules["embed"] = "data"
    if decode and tp is not None and cfg is not None and cfg.num_kv_heads \
            and cfg.num_kv_heads % _axis_size(mesh, tp) != 0:
        # Distributed flash-decode: the cache is seq-sharded (kv_heads can't
        # shard). If q stayed heads-sharded, GSPMD must all-gather the WHOLE
        # cache every token (190 GB/token for deepseek-67b — §Perf iteration
        # 3). Replicating the q *activation* instead (weights stay sharded)
        # lets GSPMD emit the online-softmax partial merge: per-shard local
        # attention + tiny [b,h]/[b,h,d] all-reduces.
        rules["heads"] = None
        # Projection WEIGHTS shard on the fused (heads·head_dim) dim — always
        # divisible even when the head count isn't. The resulting activation
        # gather is one [B,1,H·D] row per token (KBs); without this, decode
        # replicated q/k/v/o projections (+24 GB/dev on llava — §Perf it. 3).
        rules["q_proj"] = tp
        rules["kv_proj"] = tp
    tp_size = _axis_size(mesh, tp) if tp else 1
    if cfg is not None and cfg.num_heads:
        if cfg.num_heads % tp_size != 0:
            # heads not divisible (qwen3 40, llava 56, rg 10 on tp=16):
            # replicate head-projections; activations fall back automatically.
            rules["heads"] = None
            rules["q_proj"] = None
            if getattr(cfg, "ctx_parallel_attn", False):
                # context parallelism: shard attention QUERY rows over the
                # model axis instead — each shard computes all heads for its
                # sequence slice (full KV), removing the tp_size× replication
                # of attention compute (perf hillclimb iteration 4).
                rules["seq_full"] = tp
        if cfg.num_kv_heads % tp_size != 0:
            rules["kv_heads"] = None
            rules["kv_proj"] = None
    return ShardingRules(mesh=mesh, rules=rules)


def _fsdp_rules(mesh: Mesh, cfg) -> ShardingRules:
    """FSDP/ZeRO-3 profile: no tensor parallelism. Batch shards over
    (data, model) jointly; every param's *embed* dim shards over the same
    axes (weights all-gathered per layer, grads reduce-scattered). Collective
    bytes scale with weight size instead of activation size — the right
    profile when TP-SP activation traffic dominates (small d_model, or
    large-batch training of dense stacks; see launch/hillclimb.py)."""
    fs: Any = tuple(a for a in ("data", "model") if a in mesh.shape) or None
    if fs is not None and len(fs) == 1:
        fs = fs[0]
    # pod stays pure gradient-replica DP so global_batch=256 still divides.
    rules: Dict[str, Any] = {
        "batch": fs,
        "seq": None, "seq_full": None,
        "heads": None, "kv_heads": None, "head_dim": None,
        "moe_groups": fs,
        "vocab": None, "embed": fs,
        "mlp": None, "expert_mlp": None,
        "q_proj": None, "kv_proj": None,
        "experts": None, "rnn": None, "state": None,
        "layers": None,
        "kv_cache_seq": None,
    }
    return ShardingRules(mesh=mesh, rules=rules)


def uses_fsdp_profile(cfg) -> bool:
    """Does this config take the full ZeRO-3 profile from ``default_rules``?

    Single source of the profile gate, shared with the dry-run / analytic
    memory model so their layout assumptions match what actually compiles:
    BOTH the ``sharding_profile="fsdp"`` annotation and the explicit
    ``fsdp=True`` opt-in are required (module docstring)."""
    return (cfg is not None
            and getattr(cfg, "sharding_profile", "tp_sp") == "fsdp"
            and getattr(cfg, "fsdp", False))


def vocab_pad_for(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


# ---------------------------------------------------------------------------
# Pallas kernels on a sharded mesh
# ---------------------------------------------------------------------------

PER_SHARD_IMPLS = ("pallas", "pallas_interpret")


def _kernel_spec(rules: ShardingRules, q_shape, kv_shape) -> P:
    """Leading ``(batch, heads)`` spec a per-shard attention kernel splits
    over: the rules' own axes, so the activations need no reshard.  Heads
    split only when q and kv heads split over the same axis (contiguous head
    slices then keep every GQA group on one shard).  Mesh axes that neither
    dim uses compute redundantly; that is recorded and warned."""
    qs = rules.spec_for(("batch", "heads"), tuple(q_shape[:2]))
    ks = rules.spec_for(("batch", "kv_heads"), tuple(kv_shape[:2]))
    spec = P(qs[0], qs[1] if qs[1] == ks[1] else None)
    used = {n for a in spec if a is not None
            for n in (a if isinstance(a, tuple) else (a,))}
    idle = [a for a in rules.mesh.axis_names
            if rules.mesh.shape[a] > 1 and a not in used]
    if idle:
        msg = (f"attention [B={q_shape[0]}, H={q_shape[1]}/{kv_shape[1]}] "
               f"runs whole on every shard of mesh axes {idle} (spec {spec})")
        rules.fallbacks["per_shard_attention"] = msg
        warnings.warn(msg, stacklevel=3)
    return spec


def attention_per_shard(rules: ShardingRules, q, k, v, *, seed=0,
                        segment_ids=None, **kw):
    """``spark_attention`` with a Pallas impl on a sharded mesh.

    GSPMD cannot partition a Mosaic kernel, so the kernel runs inside
    ``shard_map`` on each shard's block of batch rows and heads
    (:func:`_kernel_spec`); the sequence stays whole.  The dropout seed is
    offset by the shard index, since the kernels hash shard-local (b, h).
    q [B, Hq, S, D], k/v [B, Hkv, S, D], segment_ids [B, S]; ``kw`` goes to
    ``spark_attention``.
    """
    from repro.core.attention import spark_attention
    from repro.distributed import shard_map
    spec = _kernel_spec(rules, q.shape, k.shape)
    axes = tuple(n for a in spec if a is not None
                 for n in (a if isinstance(a, tuple) else (a,)))
    args = (q, k, v, jnp.asarray(seed, jnp.int32))
    specs = (spec, spec, spec, P())
    if segment_ids is not None:
        args += (jnp.asarray(segment_ids, jnp.int32),)
        specs += (P(spec[0]),)

    def local(q_l, k_l, v_l, seed_l, seg_l=None):
        if axes:
            seed_l = seed_l + jax.lax.axis_index(axes) * 7919
        return spark_attention(q_l, k_l, v_l, seed=seed_l, segment_ids=seg_l,
                               **kw)

    return shard_map(local, mesh=rules.mesh, in_specs=specs,
                     out_specs=spec)(*args)


def decode_per_shard(rules: ShardingRules, q, k, v, kv_len, **kw):
    """``spark_decode`` with a Pallas impl on a sharded mesh, per shard as
    in :func:`attention_per_shard`.  q [B, Hq, D], k/v [B, Hkv, S, D],
    kv_len [B]; ``kw`` goes to ``spark_decode``."""
    from repro.core.attention import spark_decode
    from repro.distributed import shard_map
    spec = _kernel_spec(rules, q.shape, k.shape)

    def local(q_l, k_l, v_l, kv_len_l):
        return spark_decode(q_l, k_l, v_l, kv_len=kv_len_l, **kw)

    return shard_map(local, mesh=rules.mesh,
                     in_specs=(spec, spec, spec, P(spec[0])),
                     out_specs=spec)(q, k, v, kv_len)
