"""What a profiler trace shows of the program's own spans and kernel names.

``bench/trace.py`` reads the harness's spans (``bench.*``) and knows a Pallas
kernel by the types it takes and returns.  The program now marks its own
work: host spans named ``repro.*`` (``src/repro/runtime/spans.py``: the
serving engine's iteration, scheduling, prefill, decode and host sampling,
and Python's garbage collections) and a ``name=`` on every ``pallas_call``,
which the compiler gives the kernel's ``tpu_custom_call`` instruction
(``%flash_fwd.3 = ... custom-call(...)`` in an ``XLA Ops`` event).  From one
``.xplane.pb`` with one ``bench.window`` span this module takes:

- ``span_s``: summed seconds inside the window of each span of either prefix;
- ``idle_by_span``: the device's idle seconds inside the window, each idle
  moment put down to the innermost span of either prefix around it (the
  shortest span that holds it, on any host thread);
- ``idle_gaps``: the ten longest idle gaps, each labelled by the innermost
  span around its middle;
- ``named_kernels``: calls and device seconds per kernel name, from leaf
  operations as in ``trace.py`` (:data:`KINDS` maps each name to the kind
  ``trace.classify_kernel`` gives it);
- ``module_ops``: for each jitted program (``XLA Modules`` event), the leaf
  device operations that took most of its time, by time containment.

It also reduces the engine's per-request host timestamps to the waits of
the requests due in a window (:func:`request_waits`).  Nothing here is read
by ``bench/run.py`` yet; ``bench/trace_report.py`` prints it.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.trace import WINDOW_SPAN, _op_label, _union

PREFIXES = ("bench.", "repro.")
OUTSIDE = "outside spans"
SAMPLE_SPANS = ("repro.engine.prefill.sample", "repro.engine.decode.sample")
# the program's kernel names, and the kind trace.classify_kernel gives each
KINDS = {"flash_fwd": "flash_fwd", "flash_bwd_dkv": "flash_bwd",
         "flash_bwd_dq": "flash_bwd", "paged_decode": "paged_decode"}


_TRANSFORMS = re.compile(r"^((?:jvp_|transpose_|vmap_)+)(.+)$")


def kernel_name(hlo_text: str) -> Optional[str]:
    """The name of the Pallas kernel an ``XLA Ops`` event is, or None for an
    operation that is no Pallas kernel.  The name is the instruction's
    without its numeric suffix and without the transforms a caller wraps it
    in: ``jvp(flash_fwd)`` compiles to ``%jvp_flash_fwd_.2``."""
    if 'custom_call_target="tpu_custom_call"' not in hlo_text:
        return None
    name = hlo_text.partition(" = ")[0].split()[-1].lstrip("%")
    name = re.sub(r"(\.\d+)+$", "", name)
    m = _TRANSFORMS.match(name)
    if m:
        n = m.group(1).count("_")
        if m.group(2).endswith("_" * n) and len(m.group(2)) > n:
            name = m.group(2)[:-n]
    return name


@dataclasses.dataclass
class ProgramReduced:
    """What a traced window showed of the program's spans; seconds."""
    window_s: float
    busy_s: float
    span_s: Dict[str, float]
    idle_by_span: Dict[str, float]
    idle_gaps: List[List]                        # [[span, seconds]], top 10
    named_kernels: Dict[str, Dict[str, float]]  # name → calls, seconds
    module_ops: Dict[str, List[List]]            # module → [[op, seconds]]


def _attribute(gaps: List[Tuple[int, int]],
               spans: List[Tuple[str, int, int]]) -> Dict[str, float]:
    """Seconds of ``gaps`` under the innermost (shortest) of ``spans``
    around each moment, ``OUTSIDE`` where no span is open."""
    edges = []
    for i, (_, a, b) in enumerate(spans):
        edges += [(a, 1, i), (b, -1, i)]
    for a, b in gaps:
        edges += [(a, 1, -1), (b, -1, -1)]
    edges.sort()
    out: Dict[str, float] = collections.Counter()
    open_spans: set = set()
    in_gap = 0
    prev = None
    for t, step, i in edges:
        if in_gap and prev is not None and t > prev:
            best = min(open_spans, key=lambda j: spans[j][2] - spans[j][1],
                       default=None)
            out[spans[best][0] if best is not None else OUTSIDE] += \
                (t - prev) * 1e-9
        prev = t
        if i < 0:
            in_gap += step
        elif step > 0:
            open_spans.add(i)
        else:
            open_spans.discard(i)
    return dict(out)


def _innermost_at(t: int, spans: List[Tuple[str, int, int]]) -> str:
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else OUTSIDE


def reduce_program_trace(path: str) -> ProgramReduced:
    """Reduce one ``.xplane.pb`` holding one ``bench.window`` span.  Device
    numbers are averaged over the TPU planes."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Tuple[str, int, int]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        s = int(e.start_ns)
                        spans.append((e.name, s, s + int(e.duration_ns)))
        elif plane.name.startswith("/device:TPU"):
            devices.append(plane)
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span in the trace, "
                           f"found {len(windows)}")
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    w0, w1 = windows[0]
    inner = [(n, max(a, w0), min(b, w1)) for n, a, b in spans
             if n != WINDOW_SPAN and min(b, w1) > max(a, w0)]

    busy = 0
    gaps: List[Tuple[int, int]] = []
    idle: Dict[str, float] = collections.Counter()
    kernels: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"calls": 0, "seconds": 0.0})
    mod_ops: Dict[str, Dict[str, float]] = collections.defaultdict(
        collections.Counter)
    for plane in devices:
        ops: List[Tuple[int, int, str]] = []
        mods: List[Tuple[int, int, str]] = []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for e in line.events:
                s = int(e.start_ns)
                t = s + int(e.duration_ns)
                if s >= w0 and t <= w1:
                    (ops if line.name == "XLA Ops" else mods).append(
                        (s, t, e.name))
        ops.sort()
        mods.sort()
        merged = _union([(s, t) for s, t, _ in ops])
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        plane_gaps = [(edges[i], edges[i + 1])
                      for i in range(0, len(edges), 2)
                      if edges[i + 1] > edges[i]]
        gaps += plane_gaps
        for k, v in _attribute(plane_gaps, inner).items():
            idle[k] += v
        # leaf operations only, as in trace.py; each to the module around it
        m = 0
        for i, (s, t, text) in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1][0] < t:
                continue
            name = kernel_name(text)
            if name is not None:
                kernels[name]["calls"] += 1
                kernels[name]["seconds"] += (t - s) * 1e-9
            while m < len(mods) and mods[m][1] <= s:
                m += 1
            if m < len(mods) and mods[m][0] <= s:
                mod_ops[mods[m][2].split("(")[0]][_op_label(text)] += \
                    (t - s) * 1e-9
    n = len(devices)
    for v in kernels.values():
        v["calls"] /= n
        v["seconds"] /= n
    span_s: Dict[str, float] = collections.Counter()
    for name, a, b in inner:
        span_s[name] += (b - a) * 1e-9
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return ProgramReduced(
        window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9 / n,
        span_s=dict(span_s),
        idle_by_span={k: v / n for k, v in idle.items()},
        idle_gaps=[[_innermost_at((a + b) // 2, inner), (b - a) * 1e-9]
                   for a, b in longest],
        named_kernels=dict(kernels),
        module_ops={mod: [[k, v / n] for k, v in sorted(
            c.items(), key=lambda kv: -kv[1])[:5]]
            for mod, c in mod_ops.items()})


def sample_idle_share(r: ProgramReduced) -> Optional[float]:
    """Percent of the time the engine had work (the window less the
    harness's waits for arrivals) in which the device sat idle while the
    host copied logits and sampled; None where the program has no such
    spans."""
    if not any(n in r.span_s for n in SAMPLE_SPANS):
        return None
    busy_window = r.window_s - r.span_s.get("bench.wait_arrival", 0.0)
    if busy_window <= 0:
        return None
    return 100.0 * sum(r.idle_by_span.get(n, 0.0)
                       for n in SAMPLE_SPANS) / busy_window


def request_waits(times: Dict[int, Tuple], due: Dict[int, float],
                  ws: float, we: float) -> Dict[str, List[float]]:
    """Per request due in the window ``[ws, we)`` (host clock), from the
    engine's ``(submitted_at, admitted_at, token_at)``:

    - ``submit_lag``: due time to submission (the harness submits only
      between engine iterations);
    - ``admit_wait``: submission to first admission, at most to ``we``;
    - ``prefill``: first admission to the first token on the host, at most
      to ``we``, for the requests admitted by then.
    """
    out: Dict[str, List[float]] = {"submit_lag": [], "admit_wait": [],
                                   "prefill": []}
    for rid, d in due.items():
        if not ws <= d < we or rid not in times:
            continue
        sub, adm, tok = times[rid][:3]
        out["submit_lag"].append(sub - d)
        admitted = min(adm, we) if adm is not None else we
        out["admit_wait"].append(admitted - sub)
        if adm is not None and adm < we:
            first = min(tok[0], we) if tok else we
            out["prefill"].append(first - adm)
    return out


def p95_ms(xs: List[float]) -> Optional[float]:
    return 1e3 * float(np.percentile(np.asarray(xs, np.float64), 95)) \
        if xs else None
