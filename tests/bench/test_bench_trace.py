"""The trace reduction: on a trace built by hand, whose numbers are known,
on real kernel operations recorded on a TPU v5e, and on a small trace
recorded there."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "hlo_ops.json")) as f:
    HLO = json.load(f)
RECORDED = os.path.join(ROOT, "bench", "testdata", "small.xplane.pb")

US = 1_000_000   # picoseconds in a microsecond

# (line, name, start us, duration us) on the device; (name, start, dur) host
DEVICE = [
    ("XLA Modules", "jit_step(123)", 10, 40),
    ("XLA Modules", "jit_step(123)", 70, 20),
    ("XLA Modules", "jit_other(9)", 120, 5),          # after the window
    ("XLA Ops", "%while.1 = (s32[]) while(s32[] %a)", 10, 40),
    ("XLA Ops", HLO["flash_fwd"], 12, 18),             # inside the loop
    ("XLA Ops", "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %b)", 30, 15),
    ("XLA Ops", HLO["flash_bwd_dq"], 70, 10),
    ("XLA Ops", "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %c)", 80, 10),
    ("XLA Ops", HLO["paged_decode"], 120, 5),          # after the window
]
HOST = [("bench.window", 5, 100), ("bench.step", 8, 44),
        ("bench.idle", 52, 16), ("bench.step", 68, 24), ("other", 0, 200)]


def build(tmp_path, device=DEVICE, host=HOST):
    from jax.profiler import ProfileData
    names = sorted({n for _, n, _, _ in device} | {n for n, _, _ in host})
    meta = {n: i + 1 for i, n in enumerate(names)}

    def events(rows):
        return "".join(
            f"events {{ metadata_id: {meta[n]} offset_ps: {s * US} "
            f"duration_ps: {d * US} }}\n" for n, s, d in rows)

    def metadata():
        return "".join(
            f"event_metadata {{ key: {i} value {{ id: {i} "
            f"name: {json.dumps(n)} }} }}\n" for n, i in meta.items())

    lines = ""
    for i, ln in enumerate(("XLA Modules", "XLA Ops")):
        rows = [(n, s, d) for l_, n, s, d in device if l_ == ln]
        lines += (f"lines {{ id: {i + 1} name: \"{ln}\" timestamp_ns: 0\n"
                  f"{events(rows)} }}\n")
    text = (f"planes {{ id: 1 name: \"/device:TPU:0\"\n{lines}{metadata()} }}\n"
            f"planes {{ id: 2 name: \"/host:CPU\"\n"
            f"lines {{ id: 9 name: \"python\" timestamp_ns: 0\n"
            f"{events(host)} }}\n{metadata()} }}\n")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_reduction_of_a_trace_with_known_numbers(tmp_path):
    r = trace.reduce_trace(build(tmp_path))
    assert r.window_s == pytest.approx(100e-6)
    # busy: [10, 50] and [70, 90]; the op after the window is left out
    assert r.busy_s == pytest.approx(60e-6)
    assert r.kernels == {
        "flash_fwd": {"calls": 1, "seconds": pytest.approx(18e-6)},
        "flash_bwd": {"calls": 1, "seconds": pytest.approx(10e-6)}}
    assert r.modules == {"jit_step": {"calls": 2,
                                      "seconds": pytest.approx(60e-6)}}
    assert r.span_s["bench.step"] == pytest.approx(68e-6)
    # gaps [5, 10], [50, 70], [90, 105]; the longest lies in bench.idle
    assert r.idle_gaps[0] == ["bench.idle", pytest.approx(20e-6)]
    assert [g[1] for g in r.idle_gaps] == pytest.approx([20e-6, 15e-6, 5e-6])
    assert r.idle_gaps[1][0] == "outside harness spans"
    # leaf operations only: the while loop holds its body's time
    ops = dict((k, v) for k, v in r.device_ops)
    assert "while" not in ops
    assert ops["fusion"] == pytest.approx(25e-6)


def test_a_trace_needs_one_window(tmp_path):
    with pytest.raises(RuntimeError, match="bench.window"):
        trace.reduce_trace(build(tmp_path, host=HOST[1:]))


@pytest.mark.parametrize("key,kind", [
    ("flash_fwd", "flash_fwd"), ("flash_bwd_dkv", "flash_bwd"),
    ("flash_bwd_dq", "flash_bwd"), ("paged_decode", "paged_decode"),
    ("fusion", None)])
def test_kernels_known_by_their_types(key, kind):
    assert trace.classify_kernel(HLO[key]) == kind


def test_recorded_trace():
    """Recorded on a TPU v5e: two training-like steps (flash forward and
    backward over packed segments), each followed by 20 ms asleep and a
    paged decode, inside one window started right after the profiler.  The
    device side of the first step was not recorded: the profiler had not
    begun to trace the device yet (so the harness now primes it before a
    window)."""
    r = trace.reduce_trace(RECORDED)
    assert r.modules["jit_train_like"]["calls"] == 1
    assert r.modules["jit_decode_like"]["calls"] == 2
    assert r.kernels["flash_fwd"]["calls"] == 1
    assert r.kernels["flash_bwd"]["calls"] == 2      # dK/dV and dQ
    assert r.kernels["paged_decode"]["calls"] == 2
    assert 0 < r.busy_s < r.window_s
    assert r.idle_gaps[0][0] == "bench.idle"
    assert r.idle_gaps[0][1] >= 0.02
    assert set(r.span_s) >= {"bench.step", "bench.idle", "bench.decode"}
