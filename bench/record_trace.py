#!/usr/bin/env python3
"""Record the small trace ``bench/testdata/named.xplane.pb`` on a TPU, for the
tests of the trace reductions.

    python3 bench/record_trace.py <out.xplane.pb>

Inside one ``bench.window`` span: two training-like steps (``jit_train_like``:
the fused MHA forward and backward over two packed documents, so the
kernels ``flash_fwd``, ``flash_bwd_dkv`` and ``flash_bwd_dq``), each followed
by 20 ms asleep under ``bench.idle`` and a paged decode (``jit_decode_like``,
the kernel ``paged_decode``) dispatched under ``repro.engine.decode`` and
fetched to the host under ``repro.engine.decode.sample``.  The device
plane reads about 1 ms ahead of the host spans, so the first step's device
side falls before the window.
"""

import os
import shutil
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness  # noqa: E402


def main(out: str) -> int:
    harness.configure_environment()
    try:
        harness.accelerator(1)
    except harness.NoAccelerator as e:
        harness.log(f"bench: {e}")
        return 2
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode import flash_paged_decode
    from repro.kernels.ops import AttnConfig, mha
    from repro.runtime.spans import span

    bf16 = jnp.bfloat16
    key = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(key[0], (1, 4, 512, 64), bf16)
    k = jax.random.normal(key[1], (1, 2, 512, 64), bf16)
    v = jax.random.normal(key[2], (1, 2, 512, 64), bf16)
    seg = jnp.asarray(np.repeat([0, 1], [300, 212])[None], jnp.int32)
    cfg = AttnConfig(causal=True)

    def train_like(q, k, v, seg):
        def loss(q, k, v):
            o = mha(q, k, v, segment_ids=seg, config=cfg)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    dq = jax.random.normal(key[3], (4, 4, 64), bf16)
    pages = jax.random.normal(key[4], (2, 64, 16, 64), bf16)
    tables = jnp.arange(32, dtype=jnp.int32).reshape(4, 8) + 1
    kv_len = jnp.asarray([100, 17, 128, 64], jnp.int32)

    def decode_like(q, pages, tables, kv_len):
        return flash_paged_decode(q, pages, pages, tables, kv_len)

    train = jax.jit(train_like)
    decode = jax.jit(decode_like)
    jax.block_until_ready(train(q, k, v, seg))
    jax.block_until_ready(decode(dq, pages, tables, kv_len))
    with tempfile.TemporaryDirectory(prefix="bench-record-") as tdir:
        harness.start_trace(tdir)
        with harness.span("window"):
            for _ in range(2):
                with harness.span("step"):
                    jax.block_until_ready(train(q, k, v, seg))
                with harness.span("idle"):
                    time.sleep(0.02)
                with span("engine.decode"):
                    o = decode(dq, pages, tables, kv_len)
                with span("engine.decode.sample"):
                    np.asarray(o)
        jax.profiler.stop_trace()
        from bench.trace import find_xplane
        shutil.copyfile(find_xplane(tdir), out)
    print(f"wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
