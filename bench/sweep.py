#!/usr/bin/env python3
"""Offered-load sweep of a serving cell, to find its knee: the highest rate
at which the waiting queue does not grow over the window.  The benchmark's
runs never run this; a cell's rate is fixed, as a number, in its traffic
file.

    python3 bench/sweep.py --workload <cell> --rates 0.3,0.4,0.5 \\
        [--seconds 90] [--seed 1]

Prints one JSON line per rate: occupancy (active and waiting requests) at
the start and end of the window and its mean, delivered tokens per second,
and the p95 time to first token.
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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=90.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    manifest = harness.load_manifest()
    cell = harness.cell_entry(manifest, args.workload)
    harness.configure_environment()
    try:
        devices = harness.accelerator(cell["chips"])
    except harness.NoAccelerator as e:
        harness.log(f"sweep: {e}")
        return 2
    harness.enable_compile_cache()
    from bench.drivers import serve
    config = harness.load_config(manifest, cell["config"])
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(gen.load_mix(cell["traffic"]), rate_per_s=rate,
                   horizon_s=args.seconds + 10)
        t = time.perf_counter()
        ctx = harness.RunContext(
            cell=args.workload, config=config, mix=mix, seed=args.seed,
            seconds=args.seconds, trace=False, devices=devices,
            limits=harness.load_limits(args.workload), t_start=t,
            compiles=harness.CompileCounter())
        res = serve.run(ctx)
        occ = res.counters["occupancy"]
        ws = res.counters["window_start_s"]
        win = [o for o in occ if o[0] >= ws]
        tenth = max(1, len(win) // 10)
        print(json.dumps({
            "rate_per_s": rate, "warmup_s": mix["warmup_s"],
            "active_first_tenth": float(np.mean([o[1] for o in win[:tenth]])),
            "active_last_tenth": float(np.mean([o[1] for o in win[-tenth:]])),
            "waiting_first_tenth": float(np.mean([o[2] for o in win[:tenth]])),
            "waiting_last_tenth": float(np.mean([o[2] for o in win[-tenth:]])),
            "active_by_10s": [float(np.mean([o[1] for o in occ
                                             if k <= o[0] < k + 10]))
                              for k in range(0, int(occ[-1][0]), 10)],
            "waiting_by_10s": [float(np.mean([o[2] for o in occ
                                              if k <= o[0] < k + 10]))
                               for k in range(0, int(occ[-1][0]), 10)],
            "end_to_end": res.end_to_end, "attempted": res.attempted,
            "token_gap": res.checks[0].value,
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
