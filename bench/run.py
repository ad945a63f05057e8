#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name: the entry in
``BENCHMARK.json``, ``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``
(whose ``kind`` names the driver in ``bench/drivers/``), the limits of its
correctness checks in ``bench/limits/<cell>.json``, and each per-layer metric's
reader in ``bench/metrics/<metric>.py``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` profiles
the measured window and reports its per-layer metrics.  Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import gen, harness  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True, type=int)
    ap.add_argument("--seconds", required=True, type=float)
    ap.add_argument("--trace", required=True, type=int, choices=(0, 1))
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be non-negative and --seconds positive")
    return args


def run_cell(args, manifest, devices, t_start, driver_kwargs=None):
    """Run the cell on ``devices``; returns (result line dict, checks)."""
    from bench import peaks as peaks_table
    from bench.counts import Dims
    from bench.trace import find_xplane, reduce_trace

    cell = harness.cell_entry(manifest, args.workload)
    config = harness.load_config(manifest, cell["config"])
    mix = gen.load_mix(cell["traffic"])
    kind = devices[0].device_kind
    peaks = peaks_table.peaks_for(kind) if devices[0].platform == "tpu" \
        else None
    driver = importlib.import_module(f"bench.drivers.{mix['kind']}")
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        ctx = harness.RunContext(
            cell=args.workload, config=config, mix=mix, seed=args.seed,
            seconds=args.seconds, trace=bool(args.trace), devices=devices,
            limits=harness.load_limits(args.workload), t_start=t_start,
            compiles=harness.CompileCounter(), trace_dir=tdir)
        res = driver.run(ctx, **(driver_kwargs or {}))
        if args.trace:
            res.reduced = reduce_trace(find_xplane(tdir))

    kinds = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in harness.cell_metrics(manifest, args.workload, kinds):
        if args.trace:
            value = harness.load_reader(m["name"])(
                res, Dims.from_config(config), peaks)
        else:
            value = res.end_to_end.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": res.memory_peak_bytes}
    line = {"correct": all(c.ok for c in res.checks),
            "attempted": res.attempted, "failed": res.failed,
            "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = res.reduced.busy_s
        device["window_s"] = res.reduced.window_s
        line["breakdown"] = {"device_ops": res.reduced.device_ops,
                             "idle_gaps": res.reduced.idle_gaps}
    line["compiles_in_window"] = res.counters.get("compiles_in_window")
    if "samples" in res.counters:
        line["samples"] = res.counters["samples"]
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in res.checks}
    return line, res.checks


def main(argv=None):
    args = parse_args(argv)
    manifest = harness.load_manifest()
    cell = harness.cell_entry(manifest, args.workload)
    harness.configure_environment()
    try:
        devices = harness.accelerator(cell["chips"])
    except harness.NoAccelerator as e:
        harness.log(f"bench: {e}")
        return 2
    harness.enable_compile_cache()
    line, checks = run_cell(args, manifest, devices, T_START)
    for c in checks:
        harness.log(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
                    f"{'' if c.ok else '  FAILED'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
