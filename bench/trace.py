"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The harness marks the measured window with a host span named ``bench.window``
and its calls into each layer with spans named ``bench.<what>``
(``jax.profiler.TraceAnnotation``); the profiler puts those host spans and the
device's operations on one clock.  From the trace this module takes:

- busy time: the union of the intervals in which an operation ran on the
  device, inside the window;
- each Pallas kernel's calls and summed device time.  The program gives its
  kernels no names yet, so a kernel is known by the types it takes and
  returns (:func:`classify_kernel`);
- each jitted program's executions and summed device time, by module name;
- the device operations that took most time and the longest idle gaps, each
  gap labelled by the innermost harness span around it.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."

_TYPE = re.compile(r"\b(pred|[subf]\d+|bf16|f8e4m3fn|f8e5m2)\[([\d,]*)\]")


def _types(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _TYPE.findall(text)]


def classify_kernel(hlo_text: str) -> Optional[str]:
    """Which of the program's Pallas kernels an ``XLA Ops`` event is, from
    its HLO text: ``flash_fwd``, ``flash_bwd`` (the dK/dV and the dQ kernel
    alike), ``paged_decode``, ``tpu_kernel`` for another Pallas kernel, or
    None for an operation that is no Pallas kernel.

    - flash_fwd returns the output and the lane-dense f32 log-sum-exp
      ``[B, H, S, 128]``, and takes no such f32 rows;
    - flash_bwd takes two of them (log-sum-exp and delta);
    - paged decode takes a page pool ``[Hkv, pages, page_size, D]`` and a
      block table ``s32[B, T]`` and returns no log-sum-exp rows.
    """
    if 'custom_call_target="tpu_custom_call"' not in hlo_text:
        return None
    lhs, _, rhs = hlo_text.partition(" custom-call(")
    result = _types(lhs.partition(" = ")[2])
    operands = _types(rhs.split("custom_call_target")[0])
    rows = sum(1 for dt, s in operands
               if dt == "f32" and len(s) == 4 and s[-1] == 128)
    out_rows = sum(1 for dt, s in result
                   if dt == "f32" and len(s) == 4 and s[-1] == 128)
    if rows >= 2:
        return "flash_bwd"
    if rows == 0 and out_rows == 1 and len(result) == 2:
        return "flash_fwd"
    pools = [s for dt, s in operands if len(s) == 4 and dt in ("bf16", "f32")]
    tables = [s for dt, s in operands if dt == "s32" and len(s) == 2]
    if pools and tables and out_rows == 0:
        return "paged_decode"
    return "tpu_kernel"


def _op_label(hlo_text: str) -> str:
    """A short stable label for a device operation: its kernel kind, else
    its instruction name without the numeric suffix."""
    kind = classify_kernel(hlo_text)
    if kind is not None:
        return kind
    name = hlo_text.partition(" = ")[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


@dataclasses.dataclass
class Reduced:
    """What a traced window showed; times in seconds."""
    window_s: float
    busy_s: float
    kernels: Dict[str, Dict[str, float]]      # kind → {"calls", "seconds"}
    modules: Dict[str, Dict[str, float]]      # jit name → {"calls", "seconds"}
    span_s: Dict[str, float]                  # harness span → summed seconds
    device_ops: List[List]                    # [[label, seconds]], top 10
    idle_gaps: List[List]                     # [[host span, seconds]], top 10


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(files)}")
    return files[0]


def reduce_trace(path: str) -> Reduced:
    """Reduce one ``.xplane.pb`` whose host spans include one
    ``bench.window``.  Device numbers are averaged over the TPU planes."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Tuple[str, int, int]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = int(e.start_ns)
                        spans.append((e.name, s, s + int(e.duration_ns)))
        elif plane.name.startswith("/device:TPU"):
            devices.append(plane)
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span in the trace, "
                           f"found {len(windows)}")
    w0, w1 = windows[0]
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")

    busy = 0
    kernels: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"calls": 0, "seconds": 0.0})
    modules: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"calls": 0, "seconds": 0.0})
    op_time: Dict[str, float] = collections.Counter()
    gaps: List[Tuple[int, int]] = []
    for plane in devices:
        ops: List[Tuple[int, int, str]] = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    s = int(e.start_ns)
                    t = s + int(e.duration_ns)
                    if s >= w0 and t <= w1:
                        ops.append((s, t, e.name))
            elif line.name == "XLA Modules":
                for e in line.events:
                    s = int(e.start_ns)
                    if s >= w0 and s + int(e.duration_ns) <= w1:
                        m = modules[e.name.split("(")[0]]
                        m["calls"] += 1
                        m["seconds"] += e.duration_ns * 1e-9
        ops.sort()
        merged = _union([(s, t) for s, t, _ in ops])
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        # leaf operations only: a while loop's interval holds its body's
        for i, (s, t, name) in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1][0] < t:
                continue
            label = _op_label(name)
            op_time[label] += (t - s) * 1e-9
            if label in ("flash_fwd", "flash_bwd", "paged_decode",
                         "tpu_kernel"):
                kernels[label]["calls"] += 1
                kernels[label]["seconds"] += (t - s) * 1e-9
    n = len(devices)
    for d in (kernels, modules):
        for v in d.values():
            v["calls"] /= n
            v["seconds"] /= n
    span_s: Dict[str, float] = collections.Counter()
    inner = [(n_, a, b) for n_, a, b in spans if n_ != WINDOW_SPAN]
    for n_, a, b in inner:
        lo, hi = max(a, w0), min(b, w1)
        if hi > lo:
            span_s[n_] += (hi - lo) * 1e-9

    def label_gap(a: int, b: int) -> str:
        mid = (a + b) // 2
        best = None
        for n_, s, t in inner:
            if s <= mid <= t and (best is None or t - s < best[1]):
                best = (n_, t - s)
        return best[0] if best else "outside harness spans"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    idle = [[label_gap(a, b), (b - a) * 1e-9] for a, b in longest]
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9 / n,
                   kernels=dict(kernels), modules=dict(modules),
                   span_s=dict(span_s),
                   device_ops=[[k, v / n] for k, v in top_ops],
                   idle_gaps=idle)
