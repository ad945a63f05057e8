"""What every driver shares: the manifest, the device check, the compile
cache, spans, the comparison that decides ``correct``, and the per-layer
metric readers, each found by its name."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import sys
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# JAX's persistent compilation cache, at a fixed path inside the checkout:
# the path is part of the cache's key, so a directory that moved would never
# hit, and only the checkout outlasts a run.
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_entry(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config named {name!r} in BENCHMARK.json")


def load_config(manifest: dict, name: str) -> dict:
    with open(os.path.join(ROOT, config_entry(manifest, name)["file"])) as f:
        c = json.load(f)
    c["name"] = name
    return c


def load_limits(cell: str) -> Dict[str, float]:
    """The limits of the numbers that decide ``correct`` in this cell."""
    with open(os.path.join(BENCH, "limits", cell + ".json")) as f:
        return json.load(f)["limits"]


def cell_metrics(manifest: dict, cell: str, kind: str) -> List[dict]:
    """The metrics a cell reports: ``kind`` "end_to_end" or "per_layer".
    A metric with a ``workloads`` list belongs to those cells; one without
    it to every cell (for a per-layer metric: every cell that reports its
    ``moves`` metric)."""
    e2e = [m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in names]


def configure_environment():
    """Before JAX starts: keep the TPU runtime's logs out of fixed paths."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def enable_compile_cache():
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def accelerator(chips: int):
    """The first ``chips`` TPU devices; raises :class:`NoAccelerator` when
    JAX finds no TPU or too few.  There is no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX finds no TPU (platform "
                            f"{devs[0].platform!r}); the benchmark runs on "
                            f"the chip only")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX finds "
                            f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts the backend compilations JAX reports while it is armed."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@contextlib.contextmanager
def span(name: str):
    """A host span on the profiler's clock (``bench.<name>``); free when no
    trace is being taken."""
    import jax
    with jax.profiler.TraceAnnotation("bench." + name):
        yield


def program_config(c: dict):
    """The program's model config for the benchmark's configuration."""
    import jax.numpy as jnp
    from repro.configs import ArchConfig
    return ArchConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        qk_norm=bool(c.get("qk_norm")),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            c["torch_dtype"]])


@dataclasses.dataclass
class Check:
    """One number that decides ``correct``, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def worst_leaf_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                   ref_grad: Dict[str, np.ndarray]) -> float:
    """Largest gap between the program's and the reference's norm of one
    leaf (a layer's slice of a stacked weight counts as its own leaf), over
    max(the reference's norm of that leaf, the median leaf's).  Leaves whose
    reference gradient is under a thousandth of the median leaf's are left
    out: under AdamW such a leaf moves by round-off alone."""
    names = sorted(ref)
    r = np.concatenate([np.asarray(ref[n], np.float64).ravel() for n in names])
    p = np.concatenate([np.asarray(prog[n], np.float64).ravel()
                        for n in names])
    g = np.concatenate([np.asarray(ref_grad[n], np.float64).ravel()
                        for n in names])
    keep = g >= 1e-3 * np.median(g)
    med = np.median(r[keep])
    gap = np.abs(p - r)[keep] / np.maximum(r[keep], med)
    return float(np.max(gap)) if np.all(np.isfinite(p)) else math.inf


@dataclasses.dataclass
class RunContext:
    """What a driver needs for one run of one cell."""
    cell: str
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    devices: Any
    limits: Dict[str, float]
    t_start: float                       # process start, host clock
    compiles: Any = None                 # a CompileCounter
    trace_dir: Optional[str] = None


@dataclasses.dataclass
class RunResult:
    """What a driver hands back."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: List[Check]
    counters: Dict[str, Any]             # what the per-layer readers read
    memory_peak_bytes: Optional[int]
    window_s: float
    reduced: Any = None                  # trace.Reduced of the traced run


def load_reader(name: str) -> Callable:
    """The per-layer metric reader ``bench/metrics/<name>.py``'s ``read``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def start_trace(log_dir: str):
    """Start the profiler for a traced window: device and host spans,
    without the Python tracer's per-call events.  A few small device calls
    and a pause follow, before the window opens: the profiler misses the
    device side of what runs right after it starts."""
    import time

    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    for _ in range(3):
        jax.block_until_ready(jnp.zeros((8, 128)) + 1)
    time.sleep(0.25)


def peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)
