#!/usr/bin/env python
"""Bring-up check: the served path and the paper's MHA training kernels on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # qwen3-14b sharded over four chips

One process drives the chip; nothing here starts another.  With no
arguments the phases are:

  (a) device  — platform, device kind and count; anything but a TPU exits
                non-zero (there is no CPU fallback);
  (b) kernels — the Pallas kernels (``impl="pallas"``) against the unfused
                f32 oracle (``impl="naive"``) at granite-3-2b and qwen3-14b
                widths: MHA forward + backward (causal, packed segments) and
                paged decode (1 and 4 KV splits).  The compiled program must
                hold a ``tpu_custom_call`` and each error stay within
                ``KERNEL_REL_ERR``;
  (c) serve   — ``repro.launch.serve --arch granite_3_2b --paged --impl
                pallas`` at published widths and full depth in bf16; every
                request must end COMPLETED;
  (d) train   — ``make_train_step(impl="pallas")`` on packed batches at
                granite-3-2b widths cut to ``TRAIN_LAYERS`` layers, against
                ``impl="xla"`` from the same params on the same batches: the
                step-0 gradients agree leaf by leaf within
                ``TRAIN_GRAD_REL_ERR``, and the losses stay finite and agree
                step by step within ``TRAIN_LOSS_TOL`` / ``TRAIN_TRAJ_TOL``.

``--four-chips`` runs only the sharded path: qwen3-14b at published widths
and full depth served on a 4-way model mesh, then the same config cut to
``COMPARE_LAYERS`` layers served on one chip and on four, tokens compared.

The last line of standard output is one JSON object
``{"ok": true, "device": {...}}``; it is printed only when every phase
passed.  Weights are random, made from ``--seed``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Largest error allowed, as max|kernel - oracle| / max|oracle| per output:
# bf16 inputs and outputs carry 8 mantissa bits (a relative rounding step of
# 2^-8 ≈ 3.9e-3), and f32-accumulated sums over ~1k keys add a few steps.
KERNEL_REL_ERR = 2e-2
# Training, the Pallas impl against the XLA impl of the same bf16 model from
# the same params and batches (only the attention impl differs); each bound
# is about 10x what a TPU v5e read:
# |loss difference| on step 0 (read 8.8e-5 and 1.2e-4);
TRAIN_LOSS_TOL = 1e-3
# |loss difference| on the later steps, whose params have moved apart by the
# two impls' rounding through AdamW (read 2.9e-5 and 1.2e-3);
TRAIN_TRAJ_TOL = 1e-2
# step-0 gradients, max |pallas - xla| / max |xla| of the worst leaf (read
# 5.7e-3, on the first layer's wk).
TRAIN_GRAD_REL_ERR = 5e-2
TRAIN_LAYERS = 4
COMPARE_LAYERS = 2

WIDTHS = {"granite_3_2b": (32, 8, 64), "qwen3_14b": (40, 8, 128)}


def log(msg):
    print(msg, flush=True)


def rel_err(got, want):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# ---------------------------------------------------------------- phase (a)
def device_info():
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"(a) device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    return info


# ---------------------------------------------------------------- phase (b)
def packed_segments(b, s):
    """Per-row packed documents; the second row ends in padding (-1)."""
    import numpy as np
    seg = np.zeros((b, s), np.int32)
    for i in range(b):
        cuts = sorted({s // 10 * (i + 1), s // 2, s - s // 8})
        bounds = [0] + cuts + [s]
        for j in range(len(bounds) - 1):
            seg[i, bounds[j]:bounds[j + 1]] = j
        if i % 2:
            seg[i, bounds[-2]:] = -1
    return seg


def check_attention(impl, hq, hkv, d, *, b, s, seed, expect_kernel):
    """MHA forward + backward, causal over packed segments: impl vs naive.
    Returns {output: relative error}."""
    import jax
    import jax.numpy as jnp
    from repro.core.attention import spark_attention

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf16 = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, hq, s, d), bf16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), bf16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), bf16)
    do = jax.random.normal(ks[3], (b, hq, s, d), bf16)
    seg = jnp.asarray(packed_segments(b, s))

    def fwd_bwd(impl_, q, k, v, do, seg):
        def f(q, k, v):
            return spark_attention(q, k, v, impl=impl_, causal=True,
                                   segment_ids=seg)
        o, vjp = jax.vjp(f, q, k, v)
        return (o,) + vjp(do)

    compiled = jax.jit(functools.partial(fwd_bwd, impl)).lower(
        q, k, v, do, seg).compile()
    if expect_kernel:
        assert "tpu_custom_call" in compiled.as_text(), \
            "no Pallas kernel in the compiled attention program"
    got = compiled(q, k, v, do, seg)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(fwd_bwd, "naive"))(*f32, seg)
    return {name: rel_err(g, w)
            for name, g, w in zip(("o", "dq", "dk", "dv"), got, want)}


def check_paged_decode(impl, hq, hkv, d, *, b, max_len, page, num_splits,
                       seed, expect_kernel):
    """Paged decode over a shuffled page pool: impl vs the gathered oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.attention import spark_paged_decode
    from repro.kernels.ops import paged_decode_reference

    rs = np.random.RandomState(seed)
    t = max_len // page
    n_pages = b * t + 1                               # page 0: trash
    kv_len = rs.randint(1, max_len + 1, size=b).astype(np.int32)
    kv_len[0] = max_len
    tables = np.zeros((b, t), np.int32)
    perm = rs.permutation(np.arange(1, n_pages))
    for i in range(b):
        used = -(-int(kv_len[i]) // page)
        tables[i, :used] = perm[i * t:i * t + used]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bf16 = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, hq, d), bf16)
    kp = jax.random.normal(ks[1], (hkv, n_pages, page, d), bf16)
    vp = jax.random.normal(ks[2], (hkv, n_pages, page, d), bf16)
    bt, kvl = jnp.asarray(tables), jnp.asarray(kv_len)

    fn = jax.jit(functools.partial(spark_paged_decode, impl=impl,
                                   num_splits=num_splits))
    compiled = fn.lower(q, kp, vp, bt, kvl).compile()
    if expect_kernel:
        assert "tpu_custom_call" in compiled.as_text(), \
            "no Pallas kernel in the compiled decode program"
    got = compiled(q, kp, vp, bt, kvl)
    with jax.default_matmul_precision("highest"):
        want = paged_decode_reference(*(x.astype(jnp.float32)
                                        for x in (q, kp, vp)), bt, kvl)
    return rel_err(got, want)


def phase_kernels(impl="pallas", *, b=2, s=1024, dec_b=8, dec_len=2048,
                  page=16, seed=0):
    expect_kernel = impl == "pallas"
    for arch, (hq, hkv, d) in WIDTHS.items():
        errs = check_attention(impl, hq, hkv, d, b=b, s=s, seed=seed,
                               expect_kernel=expect_kernel)
        log(f"(b) {arch} mha fwd+bwd causal packed [B={b} S={s} "
            f"H={hq}/{hkv} D={d}] max rel err: "
            + " ".join(f"{n}={e:.3e}" for n, e in errs.items())
            + f" (bound {KERNEL_REL_ERR})")
        for ns in (1, 4):
            err = check_paged_decode(impl, hq, hkv, d, b=dec_b,
                                     max_len=dec_len, page=page,
                                     num_splits=ns, seed=seed,
                                     expect_kernel=expect_kernel)
            errs[f"paged_decode_ns{ns}"] = err
            log(f"(b) {arch} paged decode num_splits={ns} [B={dec_b} "
                f"kv<={dec_len} page={page}] max rel err: {err:.3e} "
                f"(bound {KERNEL_REL_ERR})")
        bad = {n: e for n, e in errs.items() if not e <= KERNEL_REL_ERR}
        assert not bad, f"{arch}: kernel errors over {KERNEL_REL_ERR}: {bad}"


# ---------------------------------------------------------------- phase (c)
def serve(argv):
    """Run ``repro.launch.serve`` and require every request to complete."""
    from repro.launch import serve as serve_cli
    from repro.serving import Outcome
    log("(c) python -m repro.launch.serve " + " ".join(argv))
    eng = serve_cli.main(argv)
    outcomes = {rid: r.outcome for rid, r in eng.results.items()}
    assert outcomes and all(o is Outcome.COMPLETED
                            for o in outcomes.values()), outcomes
    return eng


def phase_serve(impl="pallas", *, arch="granite_3_2b", extra=(),
                requests=8, prompt_len=512, gen=32, seed=0):
    argv = ["--arch", arch, "--paged", "--impl", impl,
            "--requests", str(requests), "--prompt-len", str(prompt_len),
            "--gen", str(gen), "--seed", str(seed), *extra]
    eng = serve(argv)
    log(f"(c) {arch}: {len(eng.results)} requests COMPLETED "
        f"({eng.cfg.num_layers} layers, dtype {eng.cfg.dtype.__name__})")


# ---------------------------------------------------------------- phase (d)
def phase_train(impl="pallas", *, cfg=None, batch=4, seq=2048, steps=3,
                seed=0):
    """The paper's workload, witnessed by the XLA impl: step-0 gradients
    leaf by leaf, then ``steps`` AdamW steps of each impl from the same
    params on the same packed batches, losses compared step by step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.data import DataConfig
    from repro.data.synthetic import make_batch
    from repro.models import lm
    from repro.models.layers import Ctx
    from repro.runtime.steps import make_train_step

    cfg = cfg or dataclasses.replace(configs.get_config("granite_3_2b"),
                                     num_layers=TRAIN_LAYERS)
    n = cfg.param_count()
    log(f"(d) train {cfg.name} cut to {cfg.num_layers} layers: {n / 1e6:.0f}M "
        f"params; bf16 params + grads {4 * n / 2**30:.1f} GiB, f32 AdamW "
        f"m/v/master {12 * n / 2**30:.1f} GiB; batch {batch}x{seq} packed")
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                      global_batch=batch, seed=seed, pack=True,
                      min_seg_len=seq // 16, max_seg_len=seq // 2)
    batches = [{k: jnp.asarray(v) for k, v in make_batch(data, i).items()}
               for i in range(steps)]
    impls = (impl, "xla")
    # the trainer launcher's default warmup: 20 steps up to the peak lr
    arts = {i: make_train_step(cfg, impl=i, total_steps=steps,
                               warmup_steps=20) for i in impls}
    key = jax.random.PRNGKey(seed)
    init = jax.jit(lambda k: arts[impl].init_fn(k)[:2])

    def grads(impl_, params, bt):
        ctx = Ctx(impl=impl_, deterministic=True)
        return jax.grad(lambda p: lm.loss_fn(cfg, p, bt, ctx)[0])(params)

    params = init(key)[0]
    g = {i: jax.jit(functools.partial(grads, i))(params, batches[0])
         for i in impls}
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(g[impl])[0]]
    leaf_err = dict(zip(paths, map(rel_err, jax.tree.leaves(g[impl]),
                                   jax.tree.leaves(g["xla"]))))
    worst = max(leaf_err, key=leaf_err.get)
    log(f"(d) step-0 gradients {impl} vs xla, {len(leaf_err)} leaves: worst "
        f"leaf {worst} max rel err {leaf_err[worst]:.3e} "
        f"(bound {TRAIN_GRAD_REL_ERR})")
    del params, g

    losses = {}
    for i in impls:
        params, opt_state = init(key)
        step_fn = arts[i].step_fn.lower(params, opt_state, batches[0],
                                        jnp.int32(0)).compile()
        if i == "pallas":
            assert "tpu_custom_call" in step_fn.as_text(), \
                "no Pallas kernel in the compiled train step"
        losses[i] = []
        for s, bt in enumerate(batches):
            t0 = time.perf_counter()
            params, opt_state, metrics = step_fn(params, opt_state, bt,
                                                 jnp.int32(s))
            losses[i].append(float(metrics["loss"]))
            log(f"(d) {i} step {s}: loss {losses[i][-1]:.5f} lr "
                f"{float(metrics['lr']):.2e} "
                f"({time.perf_counter() - t0:.2f}s host wall, incl. transfer)")
        del params, opt_state
    diffs = [abs(a - b) for a, b in zip(losses[impl], losses["xla"])]
    log(f"(d) loss |{impl} - xla| by step: "
        + " ".join(f"{d:.3e}" for d in diffs)
        + f" (bounds {TRAIN_LOSS_TOL} at step 0, {TRAIN_TRAJ_TOL} after)")
    assert all(np.isfinite(losses[i]).all() for i in impls), losses
    assert leaf_err[worst] <= TRAIN_GRAD_REL_ERR, (worst, leaf_err[worst])
    assert diffs[0] <= TRAIN_LOSS_TOL, losses
    assert max(diffs[1:], default=0.0) <= TRAIN_TRAJ_TOL, losses


# ------------------------------------------------------------- four chips
def phase_four_chips(impl="pallas", *, n=4, arch="qwen3_14b", full=None,
                     compare_cfg=None, requests=8, prompt_len=512, gen=32,
                     seed=0):
    """qwen3-14b sharded over ``n`` chips; then a depth cut served on one
    chip and on ``n``, tokens compared."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.launch.train import parse_mesh
    from repro.runtime.steps import init_serve_params
    from repro.serving import PagedCacheConfig, ServingEngine

    assert len(jax.devices()) >= n, f"needs {n} devices"
    serve(list(full) if full is not None else
          ["--arch", arch, "--paged", "--mesh", str(n), "--impl", impl,
           "--requests", str(requests), "--prompt-len", str(prompt_len),
           "--gen", str(gen), "--seed", str(seed)])
    log(f"(4) {arch} full depth on a {n}-way model mesh: all COMPLETED")

    # Token identity needs the two layouts to compute the same logits: f32
    # at full matmul precision, since a sharded matmul sums in another order
    # and bf16 noise would flip near-tied argmaxes of a random model.
    cfg = compare_cfg or dataclasses.replace(
        configs.get_config(arch), num_layers=COMPARE_LAYERS,
        dtype=jnp.float32)
    rs = np.random.RandomState(seed)
    reqs = [(rs.randint(0, cfg.vocab_size,
                        size=int(rs.randint(prompt_len // 4, prompt_len + 1))
                        ).astype(np.int32), gen) for _ in range(requests)]
    budget = prompt_len + gen
    per_seq = -(-budget // 16) + 1

    def run(mesh):
        shards = 1 if mesh is None else n
        pcfg = PagedCacheConfig(page_size=16, max_batch=4,
                                max_pages_per_seq=per_seq,
                                num_pages=-(-(shards + 4 * per_seq) // shards)
                                * shards, num_shards=shards)
        params = init_serve_params(cfg, jax.random.PRNGKey(seed), mesh)
        with jax.default_matmul_precision("highest"):
            eng = ServingEngine(cfg, pcfg, params, impl=impl, mesh=mesh,
                                prefill_len=prompt_len)
            out, _ = eng.run(list(reqs))
        assert len(out) == len(reqs), eng.results
        return out

    one = run(None)
    gc.collect()
    many = run(parse_mesh(str(n)))
    same = sum(np.array_equal(one[r], many[r]) for r in one)
    log(f"(4) {cfg.name} cut to {cfg.num_layers} layers, f32: one chip vs "
        f"{n} chips: {same}/{len(one)} requests token-identical")
    assert same == len(one), {r: (one[r], many[r]) for r in one}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only qwen3-14b sharded over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"no TPU found (platform {dev['platform']!r}): nothing run",
              file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(seed=args.seed)
    else:
        phase_kernels(seed=args.seed)
        phase_serve(seed=args.seed)
        phase_train(seed=args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
