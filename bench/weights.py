"""Random weights made from ``--seed`` on the device, in one jitted call.

The benchmark makes the weights, not the program, so that the plain
reference can make the very same ones again from the seed without taking
anything the program made.  The pytree has the layout the program's dense
decoder takes (``repro.models.lm``: layers stacked under
``blocks/sub_0``); the values are the benchmark's own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def shapes(c: dict) -> dict:
    """{path: shape} of every weight, in the program's layout."""
    L, d = c["num_hidden_layers"], c["hidden_size"]
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    ff, v = c["intermediate_size"], c["vocab_size"]
    out = {
        "embed": (v, d), "final_norm": (d,), "lm_head": (d, v),
        "blocks/sub_0/norm1": (L, d), "blocks/sub_0/norm2": (L, d),
        "blocks/sub_0/mixer/wq": (L, d, hq * hd),
        "blocks/sub_0/mixer/wk": (L, d, hkv * hd),
        "blocks/sub_0/mixer/wv": (L, d, hkv * hd),
        "blocks/sub_0/mixer/wo": (L, hq * hd, d),
        "blocks/sub_0/mlp/wi": (L, d, 2 * ff),
        "blocks/sub_0/mlp/wo": (L, ff, d),
    }
    if c.get("qk_norm"):
        out["blocks/sub_0/mixer/q_norm"] = (L, hd)
        out["blocks/sub_0/mixer/k_norm"] = (L, hd)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, x in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return tree


def make_params(c: dict, seed: int):
    """All weights of config ``c`` in its served dtype: norms near 1,
    the embedding at 0.02, each matmul weight at 1/sqrt(fan-in)."""
    dtype = DTYPES[c["torch_dtype"]]
    flat_shapes = shapes(c)

    @functools.partial(jax.jit, static_argnums=())
    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(sorted(flat_shapes.items())):
            k = jax.random.fold_in(key, i)
            name = path.rsplit("/", 1)[-1]
            if name.endswith("norm") or name.startswith("norm"):
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif name == "embed":
                x = 0.02 * jax.random.normal(k, shape, jnp.float32)
            else:
                x = jax.random.normal(k, shape, jnp.float32) \
                    * shape[-2] ** -0.5
            flat[path] = x.astype(dtype)
        return _nest(flat)

    return make(seed_key(seed))
