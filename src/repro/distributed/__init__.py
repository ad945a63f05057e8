from repro.distributed.sharding import (ShardingRules, default_rules,
                                        vocab_pad_for)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with varying-mesh-axes checking off.

    The distributed attention paths wrap ``pallas_call``, whose out_shapes
    carry no varying-mesh-axes info, so the checker rejects them spuriously.
    """
    import jax
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


__all__ = ["ShardingRules", "default_rules", "vocab_pad_for", "shard_map"]
