#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's, the control's
and the planted faults', at a cell's own size, for several seeds in one
process.  The benchmark's own runs never run this.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 30]

Training cells: the plain reference computed in float8 (e4m3) matmuls, the
precision below the configuration's bfloat16, is put in the program's
place, and so is the float32 reference with half of the batch left out (the
loss the mean over the rest); each is compared with the float32 reference as
a run compares the program.  A step that returns its state unchanged reads 1
by that measure and needs no run.

Serving cells: one run of the cell as the benchmark makes it, after which
the sampled requests are compared twice: the program's served tokens, and at
each position the token the float8 reference ranks first.

Prints one JSON line per seed.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import gen, harness  # noqa: E402


def half_batch(batch):
    """The half-batch fault: the second half of every row marked as
    padding, so the loss is the mean over the rest."""
    seg = np.array(batch["segment_ids"])
    seg[:, seg.shape[1] // 2:] = -1
    return dict(batch, segment_ids=seg)


def train_readings(ctx):
    """Gaps of the float8 control and of the half-batch fault against the
    float32 reference, for one seed."""
    from bench import reference, weights
    c, mix = ctx.config, ctx.mix
    n = mix["checked_steps"]
    batches = []
    for st in gen.train_steps(mix, ctx.seed, c["vocab_size"]):
        batches.append(st.batch)
        if len(batches) == n:
            break

    def make():
        return weights.make_params(c, ctx.seed)

    ref = reference.train_readings(c, reference.Num(), make, batches, n)
    out = {}
    for name, num, bs in (
            ("control_f8", reference.Num(low=True), batches),
            ("half_batch", reference.Num(), [half_batch(b) for b in batches])):
        got = reference.train_readings(c, num, make, bs, n)
        out[name] = {
            "loss_gap": max(abs(a - b) for a, b in zip(got[0], ref[0])),
            "grad_gap": harness.worst_leaf_gap(got[1], ref[1], ref[1]),
            "update_gap": harness.worst_leaf_gap(got[2], ref[2], ref[1])}
    out["ref_losses"] = ref[0]
    return out


def serve_readings(ctx):
    """The program's token gap and the float8 control's, for one seed."""
    from bench.drivers import serve
    res = serve.run(ctx, control=True)
    return {"program": {"token_gap": res.checks[0].value},
            "control_f8": {"token_gap": res.counters["control_token_gap"]},
            "served_tokens_compared": res.counters["tokens_compared"],
            "attempted": res.attempted}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    manifest = harness.load_manifest()
    cell = harness.cell_entry(manifest, args.workload)
    harness.configure_environment()
    try:
        devices = harness.accelerator(cell["chips"])
    except harness.NoAccelerator as e:
        harness.log(f"control: {e}")
        return 2
    harness.enable_compile_cache()
    config = harness.load_config(manifest, cell["config"])
    mix = gen.load_mix(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ctx = harness.RunContext(
            cell=args.workload, config=config, mix=mix, seed=seed,
            seconds=args.seconds, trace=False, devices=devices,
            limits=harness.load_limits(args.workload), t_start=t,
            compiles=harness.CompileCounter())
        read = train_readings if mix["kind"] == "train" else serve_readings
        out = dict(read(ctx), seed=seed, seconds=time.perf_counter() - t)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
