#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print what the program's own
spans, kernel names and request timestamps show.

    python3 bench/trace_report.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--keep DIR]

The run is ``bench/run.py``'s: the same driver, window and checks.  One JSON
line follows, with ``correct``, the checks and the end-to-end metrics (also
with ``--trace 1``, so that a traced and an untraced run of one seed show
what tracing costs).  With ``--trace 1`` it adds the accepted per-layer
metrics as ``bench/run.py`` reads them, and under ``program`` the reduction
of the same trace by ``bench/program_trace.py``: device idle put down to the
innermost span of either prefix, the longest idle gaps so labelled, the
kernels by name beside the kernels by type, the top operations of each
jitted program, and ``sample_idle``.  In a serving cell ``requests`` gives
the p95, over the requests due in the window, of the engine's timestamps:
due to submitted, submitted to first admitted, admitted to first token.
Python's garbage collections are traced as ``repro.host.gc`` spans.  Run on
a program without these spans or timestamps, those keys read null.  The
trace is kept under ``--keep`` when given.
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

from bench import gen, harness, program_trace  # noqa: E402


def keep_request_times(store: dict):
    """Have each serving engine built from here on leave its requests'
    timestamps in ``store`` when it is freed: the serve driver frees its
    engine before it returns.  A program whose engine keeps none leaves
    ``store`` empty."""
    import repro.serving as serving
    base = serving.ServingEngine
    if not hasattr(base, "request_times"):
        return

    class Kept(base):
        def __del__(self):
            store.update(self.request_times())

    serving.ServingEngine = Kept


def trace_gc():
    try:
        from repro.runtime.spans import trace_gc as install
    except ImportError:      # a program without its own spans
        return
    install()


def report(args, manifest, devices, tdir, driver_kwargs=None) -> dict:
    from bench import peaks as peaks_table
    from bench.counts import Dims
    from bench.trace import find_xplane, reduce_trace

    cell = harness.cell_entry(manifest, args.workload)
    config = harness.load_config(manifest, cell["config"])
    mix = gen.load_mix(cell["traffic"])
    driver = importlib.import_module(f"bench.drivers.{mix['kind']}")
    times: dict = {}
    keep_request_times(times)
    ctx = harness.RunContext(
        cell=args.workload, config=config, mix=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), devices=devices,
        limits=harness.load_limits(args.workload), t_start=T_START,
        compiles=harness.CompileCounter(), trace_dir=tdir)
    res = driver.run(ctx, **(driver_kwargs or {}))
    line = {"workload": args.workload, "seed": args.seed,
            "trace": args.trace,
            "correct": all(c.ok for c in res.checks),
            "checks": {c.name: c.value for c in res.checks},
            "end_to_end": res.end_to_end,
            "compiles_in_window": res.counters.get("compiles_in_window")}
    if args.trace:
        xplane = find_xplane(tdir)
        res.reduced = reduce_trace(xplane)
        peaks = peaks_table.peaks_for(devices[0].device_kind)
        line["per_layer"] = {
            m["name"]: harness.load_reader(m["name"])(
                res, Dims.from_config(config), peaks)
            for m in harness.cell_metrics(manifest, args.workload,
                                          "per_layer")}
        line["breakdown"] = {"device_ops": res.reduced.device_ops,
                             "idle_gaps": res.reduced.idle_gaps}
        pr = program_trace.reduce_program_trace(xplane)
        line["program"] = {
            "window_s": pr.window_s, "busy_s": pr.busy_s,
            "sample_idle": program_trace.sample_idle_share(pr),
            "idle_by_span": dict(sorted(pr.idle_by_span.items(),
                                        key=lambda kv: -kv[1])),
            "idle_gaps": pr.idle_gaps,
            "span_s": dict(sorted(pr.span_s.items(),
                                  key=lambda kv: -kv[1])),
            "named_kernels": pr.named_kernels,
            "kernels": res.reduced.kernels,
            "module_ops": pr.module_ops}
    if times:
        ws = T_START + res.end_to_end["setup_s"]
        t0 = ws - res.counters["window_start_s"]
        due = {r.rid: t0 + r.due_s for r in gen.serve_requests(
            mix, args.seed, config["vocab_size"])}
        waits = program_trace.request_waits(times, due, ws,
                                            ws + args.seconds)
        line["requests"] = {k + "_p95_ms": program_trace.p95_ms(v)
                            for k, v in waits.items()}
        line["requests"]["n"] = len(waits["admit_wait"])
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True, type=int)
    ap.add_argument("--seconds", required=True, type=float)
    ap.add_argument("--trace", required=True, type=int, choices=(0, 1))
    ap.add_argument("--keep", default="",
                    help="keep the trace under this directory")
    args = ap.parse_args(argv)
    manifest = harness.load_manifest()
    cell = harness.cell_entry(manifest, args.workload)
    harness.configure_environment()
    try:
        devices = harness.accelerator(cell["chips"])
    except harness.NoAccelerator as e:
        harness.log(f"bench: {e}")
        return 2
    harness.enable_compile_cache()
    if args.trace:
        trace_gc()
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        line = report(args, manifest, devices, args.keep)
    else:
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
            line = report(args, manifest, devices, tdir)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
