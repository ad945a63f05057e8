"""The reduction of the program's own spans, kernel names and request
timestamps (``bench/program_trace.py``): on a trace built by hand, whose
numbers are known, on a small trace recorded on a TPU v5e, and through
``bench/trace_report.py`` on the CPU at a tiny size."""

import argparse
import json
import math
import os
import re
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, program_trace, trace  # noqa: E402
from bench import trace_report  # noqa: E402
from test_bench_faults import tiny_cells  # noqa: E402,F401

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "hlo_ops.json")) as f:
    HLO = json.load(f)
RECORDED = os.path.join(ROOT, "bench", "testdata", "named.xplane.pb")
M = harness.load_manifest()

US = 1_000_000   # picoseconds in a microsecond


def named(key, name):
    """A recorded kernel's HLO text under the instruction name that the
    kernel's ``name=`` gives it."""
    return re.sub(r"^%\S+ =", f"%{name}.3 =", HLO[key])


# (line, name, start us, duration us) on the device; (name, start, dur) host
DEVICE = [
    ("XLA Modules", "jit_prefill_fn(1)", 10, 40),
    ("XLA Modules", "jit_decode_fn(2)", 70, 20),
    ("XLA Ops", "%while.1 = (s32[]) while(s32[] %a)", 10, 40),
    ("XLA Ops", named("flash_fwd", "flash_fwd"), 12, 18),
    ("XLA Ops", "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %b)", 30, 15),
    ("XLA Ops", named("paged_decode", "paged_decode"), 70, 10),
    ("XLA Ops", "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %c)", 80, 10),
]
HOST = [("bench.window", 5, 100), ("bench.iteration", 6, 95),
        ("repro.engine.step", 8, 92), ("repro.engine.prefill", 8, 12),
        ("repro.engine.prefill.sample", 20, 40),
        ("repro.engine.decode", 60, 12),
        ("repro.engine.decode.sample", 72, 28), ("other", 0, 200)]
GC = [("repro.host.gc", 52, 4)]          # on a line of its own


def build(tmp_path):
    from jax.profiler import ProfileData
    names = sorted({n for _, n, _, _ in DEVICE}
                   | {n for n, _, _ in HOST + GC})
    meta = {n: i + 1 for i, n in enumerate(names)}

    def events(rows):
        return "".join(
            f"events {{ metadata_id: {meta[n]} offset_ps: {s * US} "
            f"duration_ps: {d * US} }}\n" for n, s, d in rows)

    def lines(groups):
        return "".join(f"lines {{ id: {i + 1} name: \"{name}\" "
                       f"timestamp_ns: 0\n{events(rows)} }}\n"
                       for i, (name, rows) in enumerate(groups))

    metadata = "".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                       f"name: {json.dumps(n)} }} }}\n"
                       for n, i in meta.items())
    device = lines([(ln, [(n, s, d) for l_, n, s, d in DEVICE if l_ == ln])
                    for ln in ("XLA Modules", "XLA Ops")])
    host = lines([("python", HOST), ("gc", GC)])
    text = (f"planes {{ id: 1 name: \"/device:TPU:0\"\n{device}{metadata} }}\n"
            f"planes {{ id: 2 name: \"/host:CPU\"\n{host}{metadata} }}\n")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_idle_goes_to_the_innermost_span_of_either_prefix(tmp_path):
    r = program_trace.reduce_program_trace(build(tmp_path))
    assert r.window_s == pytest.approx(100e-6)
    assert r.busy_s == pytest.approx(60e-6)
    # gaps [5, 10], [50, 70], [90, 105], split where spans open and close
    expect = {"outside spans": 5, "bench.iteration": 3,
              "repro.engine.prefill": 2, "repro.engine.prefill.sample": 6,
              "repro.host.gc": 4, "repro.engine.decode": 10,
              "repro.engine.decode.sample": 10}
    assert r.idle_by_span == pytest.approx(
        {k: v * 1e-6 for k, v in expect.items()})
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s)
    assert [g[0] for g in r.idle_gaps] == [
        "repro.engine.decode", "repro.engine.decode.sample",
        "bench.iteration"]
    assert r.span_s["repro.engine.step"] == pytest.approx(92e-6)
    assert "other" not in r.span_s and "bench.window" not in r.span_s
    # 16 of the 100 us the engine had work sat idle while it sampled
    assert program_trace.sample_idle_share(r) == pytest.approx(16.0)


def test_kernels_by_name_and_ops_by_module(tmp_path):
    path = build(tmp_path)
    r = program_trace.reduce_program_trace(path)
    assert r.named_kernels == {
        "flash_fwd": {"calls": 1, "seconds": pytest.approx(18e-6)},
        "paged_decode": {"calls": 1, "seconds": pytest.approx(10e-6)}}
    # the same kernels as the reduction that knows them by their types
    assert trace.reduce_trace(path).kernels == r.named_kernels
    ops = {m: dict(v) for m, v in r.module_ops.items()}
    assert ops == {
        "jit_prefill_fn": {"flash_fwd": pytest.approx(18e-6),
                           "fusion": pytest.approx(15e-6)},
        "jit_decode_fn": {"paged_decode": pytest.approx(10e-6),
                          "fusion": pytest.approx(10e-6)}}


@pytest.mark.parametrize("key,name", [
    ("flash_fwd", "flash_fwd"), ("flash_bwd_dkv", "flash_bwd_dkv"),
    ("flash_bwd_dq", "flash_bwd_dq"), ("paged_decode", "paged_decode")])
def test_kernel_names_map_to_the_kinds_of_their_types(key, name):
    text = named(key, name)
    assert program_trace.kernel_name(text) == name
    assert program_trace.KINDS[name] == trace.classify_kernel(text)


@pytest.mark.parametrize("inst,name", [
    ("jvp_flash_fwd_.2", "flash_fwd"),
    ("transpose_jvp_flash_bwd_dkv__.7", "flash_bwd_dkv"),
    ("jvp__.1", "jvp__")])
def test_kernel_names_lose_the_transforms_around_them(inst, name):
    """Under a transform the instruction carries it: ``jvp(flash_fwd)``
    compiles to ``%jvp_flash_fwd_.2`` (recorded on a TPU v5e); an unnamed
    kernel keeps what the compiler called it."""
    text = re.sub(r"^%\S+ =", f"%{inst} =", HLO["flash_fwd"])
    assert program_trace.kernel_name(text) == name


def test_an_operation_that_is_no_kernel_has_no_name():
    assert program_trace.kernel_name(HLO["fusion"]) is None


def test_sample_idle_reads_nothing_without_the_programs_spans():
    r = program_trace.ProgramReduced(
        window_s=1.0, busy_s=0.5, span_s={"bench.iteration": 1.0},
        idle_by_span={"bench.iteration": 0.5}, idle_gaps=[],
        named_kernels={}, module_ops={})
    assert program_trace.sample_idle_share(r) is None


def test_request_waits_censor_at_the_window_end():
    ws, we = 100.0, 110.0
    due = {1: 99.0, 2: 101.0, 3: 103.0, 4: 108.0, 5: 110.0}
    times = {
        1: (99.1, 99.2, [99.5]),               # due before the window
        2: (101.3, 101.4, [101.9, 102.3]),
        3: (103.2, 109.0, []),                 # no token by the window's end
        4: (108.5, None, []),                  # never admitted in the window
        5: (110.2, 110.3, [110.5]),            # due at the window's end
    }
    w = program_trace.request_waits(times, due, ws, we)
    assert w["submit_lag"] == pytest.approx([0.3, 0.2, 0.5])
    assert w["admit_wait"] == pytest.approx([0.1, 5.8, 1.5])
    assert w["prefill"] == pytest.approx([0.5, 1.0])
    assert program_trace.p95_ms([]) is None
    assert program_trace.p95_ms([0.1, 0.2]) == pytest.approx(195.0)


def test_recorded_trace_names_each_kernel_as_its_types_do():
    """Recorded on a TPU v5e by ``bench/record_trace.py``: two training-like
    steps and two paged decodes under ``repro.engine.*`` spans.  The device
    side of the first step lies before the window on the trace's clock (the
    device plane reads about 1 ms ahead of the host spans that dispatch its
    work), so the window holds one step, with the kernels under their
    transforms' names (``jvp_flash_fwd_``).  Mapped to the kinds of
    ``trace.classify_kernel``, the kernels by name are the kernels by type,
    in calls and in seconds."""
    r = program_trace.reduce_program_trace(RECORDED)
    assert set(r.named_kernels) == {"flash_fwd", "flash_bwd_dkv",
                                    "flash_bwd_dq", "paged_decode"}
    kinds = {}
    for name, k in r.named_kernels.items():
        v = kinds.setdefault(program_trace.KINDS[name],
                             {"calls": 0, "seconds": 0.0})
        v["calls"] += k["calls"]
        v["seconds"] += k["seconds"]
    typed = trace.reduce_trace(RECORDED).kernels
    assert set(typed) == set(kinds)
    for kind, v in typed.items():
        assert kinds[kind]["calls"] == v["calls"]
        assert kinds[kind]["seconds"] == pytest.approx(v["seconds"],
                                                       rel=1e-12)
    assert {"repro.engine.decode", "repro.engine.decode.sample"} \
        <= set(r.span_s)
    assert r.module_ops["jit_train_like"][0][0] in ("flash_fwd",
                                                   "flash_bwd")


@pytest.mark.time_limit(240)
@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]
                                  if "serve" in w["name"]])
def test_report_reads_the_engines_request_timestamps(tiny_cells, cell):
    args = argparse.Namespace(workload=cell, seed=2 ** 31 + 11, seconds=1.0,
                              trace=0, keep="")
    line = trace_report.report(args, M, jax.devices(), None,
                               driver_kwargs={"impl": "xla"})
    assert line["correct"], line["checks"]
    req = line["requests"]
    assert req["n"] >= 1
    for k in ("submit_lag_p95_ms", "admit_wait_p95_ms", "prefill_p95_ms"):
        assert req[k] is not None and 0 <= req[k] < 60e3, (k, req)
    assert math.isfinite(line["end_to_end"]["setup_s"])
    json.dumps(line)
