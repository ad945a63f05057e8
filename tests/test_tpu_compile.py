"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode cannot see Mosaic's block-tiling rule, its VMEM limit or a
kernel that fails to lower; the TPU compiler installed with JAX can, for a
chip that is described and not attached.  Each case lowers one kernel at the
real widths of granite-3-2b (32/8 heads, D=64) and qwen3-14b (40/8, D=128)
and compiles it for one chip of a described ``v5e:2x2`` topology.  Nothing
runs: a pass proves the chip's compiler accepts the kernel and that the
program really holds it (``tpu_custom_call``) under the name the profiler's
trace will show, never that it is correct or fast.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, so under
several pytest workers only the worker given this file loads it.  All TPU
compiles live in this one file for the same reason.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode import (flash_decode, flash_paged_decode,
                                  flash_paged_decode_partials)
from repro.kernels.flash_bwd import flash_bwd
from repro.kernels.flash_fwd import flash_fwd

# (heads, kv_heads, head_dim) of the two served configs
WIDTHS = {"granite_3_2b": (32, 8, 64), "qwen3_14b": (40, 8, 128)}
TRAIN_B, TRAIN_S = 2, 1024
DEC_B, DEC_S, PAGE, POOL_PAGES = 8, 2048, 16, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_tpu(one_chip):
    """Lower + compile ``fn`` on shapes for one described chip, with the
    persistent compilation cache off (a TPU entry written here could not be
    read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _kernel_names(text):
    """Names of the Pallas kernels in compiled HLO text: each
    ``tpu_custom_call`` instruction's name without its numeric suffix."""
    return {re.sub(r"(\.\d+)+$", "",
                   line.split(" = ")[0].split()[-1].lstrip("%"))
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


def _assert_kernel(compiled, *names):
    """The compiled program holds a Pallas kernel, and each of ``names``
    among its kernels."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert set(names) <= _kernel_names(text), _kernel_names(text)


@pytest.mark.parametrize("arch", sorted(WIDTHS))
@pytest.mark.parametrize("variant", ["plain", "causal", "packed"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles(compile_tpu, arch, variant, direction):
    hq, hkv, d = WIDTHS[arch]
    causal = variant != "plain"
    bf16 = jnp.bfloat16
    shapes = [((TRAIN_B, hq, TRAIN_S, d), bf16),
              ((TRAIN_B, hkv, TRAIN_S, d), bf16),
              ((TRAIN_B, hkv, TRAIN_S, d), bf16)]
    if variant == "packed":
        shapes.append(((TRAIN_B, TRAIN_S), jnp.int32))

    def fn(q, k, v, seg=None):
        o, lse = flash_fwd(q, k, v, causal=causal, segment_ids=seg)
        if direction == "fwd":
            return o, lse
        return flash_bwd(q, k, v, o, lse, o, causal=causal, segment_ids=seg)

    names = {"fwd": ["flash_fwd"],
             "bwd": ["flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"]}
    _assert_kernel(compile_tpu(fn, *shapes), *names[direction])


@pytest.mark.parametrize("arch", sorted(WIDTHS))
@pytest.mark.parametrize("num_splits", [1, 4])
def test_contiguous_decode_compiles(compile_tpu, arch, num_splits):
    hq, hkv, d = WIDTHS[arch]
    bf16 = jnp.bfloat16

    def fn(q, k, v, kv_len):
        return flash_decode(q, k, v, kv_len=kv_len, num_splits=num_splits)

    _assert_kernel(compile_tpu(fn, ((DEC_B, hq, d), bf16),
                               ((DEC_B, hkv, DEC_S, d), bf16),
                               ((DEC_B, hkv, DEC_S, d), bf16),
                               ((DEC_B,), jnp.int32)), "decode")


def _paged_shapes(hq, hkv, d):
    bf16 = jnp.bfloat16
    return [((DEC_B, hq, d), bf16),
            ((hkv, POOL_PAGES, PAGE, d), bf16),
            ((hkv, POOL_PAGES, PAGE, d), bf16),
            ((DEC_B, DEC_S // PAGE), jnp.int32),
            ((DEC_B,), jnp.int32)]


@pytest.mark.parametrize("arch", sorted(WIDTHS))
@pytest.mark.parametrize("num_splits", [1, 4])
def test_paged_decode_compiles(compile_tpu, arch, num_splits):
    def fn(q, kp, vp, bt, kv_len):
        return flash_paged_decode(q, kp, vp, bt, kv_len,
                                  num_splits=num_splits)

    _assert_kernel(compile_tpu(fn, *_paged_shapes(*WIDTHS[arch])),
                   "paged_decode")


@pytest.mark.parametrize("arch", sorted(WIDTHS))
@pytest.mark.parametrize("num_splits", [1, 2])
def test_paged_decode_partials_compiles(compile_tpu, arch, num_splits):
    shapes = _paged_shapes(*WIDTHS[arch])
    shapes.append((shapes[3][0], jnp.int32))          # block_valid [B, T]

    def fn(q, kp, vp, bt, kv_len, valid):
        return flash_paged_decode_partials(q, kp, vp, bt, kv_len,
                                           block_valid=valid,
                                           num_splits=num_splits)

    _assert_kernel(compile_tpu(fn, *shapes), "paged_decode_partials")


# ---------------------------------------------------------------------------
# whole jitted steps on the described chips as a mesh: GSPMD refuses to
# partition a Mosaic kernel, so these pass only if the kernels run per shard
# ---------------------------------------------------------------------------

def _mesh(topo, spec):
    import numpy as np
    from jax.sharding import AxisType, Mesh
    shape = tuple(int(x) for x in spec.split("x"))
    axes = ("model",) if len(shape) == 1 else ("data", "model")
    return Mesh(np.asarray(topo.devices[:4]).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))


def _abstract(tree, shardings):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


def _replicated(mesh, tree):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=NamedSharding(mesh, P())), tree)


def _granite():
    """granite-3-2b at its published widths, cut to one layer and an 8192
    vocabulary (the vocabulary only adds compile time: 43 s against 9 s)."""
    import dataclasses
    from repro import configs
    return dataclasses.replace(configs.get_config("granite_3_2b"),
                               num_layers=1, vocab_size=8192)


@pytest.mark.parametrize("mesh_spec", ["2x2", "4"])
def test_sharded_train_step_compiles(topo, compile_tpu, mesh_spec):
    """make_train_step(impl="pallas") on packed batches over four chips."""
    from repro.runtime.steps import make_train_step
    cfg, mesh = _granite(), _mesh(topo, mesh_spec)
    arts = make_train_step(cfg, mesh=mesh, impl="pallas")
    p, o = jax.eval_shape(lambda k: arts.init_fn(k)[:2],
                          jax.random.PRNGKey(0))
    sh = arts.shardings
    batch = {k: jax.ShapeDtypeStruct((4, 256), jnp.int32,
                                     sharding=sh["batch"][k])
             for k in ("tokens", "labels", "segment_ids", "positions")}
    step = _replicated(mesh, jax.ShapeDtypeStruct((), jnp.int32))
    compiled = arts.step_fn.lower(_abstract(p, sh["params"]),
                                  _abstract(o, sh["opt"]), batch,
                                  step).compile()
    _assert_kernel(compiled, "flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")


@pytest.mark.parametrize("mesh_spec", ["2x2", "4"])
def test_sharded_contiguous_serve_compiles(topo, compile_tpu, mesh_spec):
    """make_serve_steps(impl="pallas") prefill and decode over four chips."""
    from repro.models import lm
    from repro.runtime.steps import make_serve_steps, serve_param_shardings
    cfg, mesh = _granite(), _mesh(topo, mesh_spec)
    arts = make_serve_steps(cfg, mesh=mesh, impl="pallas", max_len=512,
                            batch=4)
    shapes, _ = lm.abstract_params(cfg, vocab_pad_to=mesh.shape["model"])
    params = _abstract(shapes, serve_param_shardings(cfg, mesh, shapes))
    caches = _replicated(mesh, jax.eval_shape(arts.cache_init_fn))
    tokens, token, position = _replicated(mesh, (
        jax.ShapeDtypeStruct((4, 256), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)))
    _assert_kernel(arts.prefill_fn.lower(params, tokens, None,
                                         caches).compile(), "flash_fwd")
    _assert_kernel(arts.decode_fn.lower(params, token, caches,
                                        position).compile(), "decode")
