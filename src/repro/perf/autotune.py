"""Launch-parameter autotuning for split-KV flash decode.

Decode is purely memory-bound (the roofline term is HBM bytes = bytes(K) +
bytes(V) — ``perf/memory_model.py``'s cache accounting), so the *only* launch
decision that matters is how to spread that fixed traffic over the machine:

  * too few grid cells (``B·Hkv·num_splits < parallelism``) and HBM sits idle
    behind an under-occupied grid — the headline serving shapes
    (``decode_32k``, ``long_500k``, small continuous-batching batches) live
    here;
  * too many splits and the fixed per-cell cost plus the O(B·Hq·(D+2)) f32
    partial-state merge pass start to dominate.

:func:`predict_time` models exactly that trade-off (LightSeq2's observation
that launch-parameter tuning is first-class kernel work, applied to the
split-KV decode of ``kernels/decode.py``):

    t_attn  = waves(B·Hkv·ns / parallelism) · (split KV bytes / HBM_BW + c₀)
    t_merge = ns·B·Hq·(D+2)·4 bytes / HBM_BW + c₁   (ns > 1 only)

:func:`plan_decode` picks ``(num_splits, block_kv)`` per decode geometry
(:class:`DecodeShape`) from the model, optionally refined by an on-device
timing sweep (pass ``sweep=``; ``benchmarks/decode_split.py`` wires one), and
memoises through a persistent JSON cache (:class:`AutotuneCache` —
``$REPRO_AUTOTUNE_CACHE`` > ``~/.cache/repro/autotune.json`` > repo-local),
keyed by the device kind as well as the geometry, so a plan made for one
device is never served on another.
``ServingEngine(autotune=True)`` / ``launch/serve.py --autotune`` call this
once per engine build; the jitted decode step then runs with a static
``num_splits``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from pathlib import Path
from typing import Callable, Dict, Optional, Sequence, Tuple

from repro.perf.memory_model import BF16
from repro.perf.roofline import HBM_BW

# Grid cells the hardware overlaps: TPU cores × the Mosaic pipeline depth a
# memory-bound kernel sustains. A modelling constant, not a probed value —
# only the *ratio* of occupancy between candidate plans matters to the argmin.
DEFAULT_PARALLELISM = 8

GRID_CELL_OVERHEAD_S = 1e-6   # c₀: per-wave dispatch/pipeline-fill cost
MERGE_OVERHEAD_S = 2e-6       # c₁: the extra merge pass's fixed cost

SPLIT_CANDIDATES = (1, 2, 4, 8, 16, 32)
BLOCK_KV_CANDIDATES = (128, 256, 512, 1024)

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"


@dataclasses.dataclass(frozen=True)
class DecodeShape:
    """The launch-relevant decode geometry — the autotune cache key.

    ``page_size == 0`` means a contiguous cache (``block_kv`` tunable);
    ``page_size > 0`` pins ``block_kv`` to the page size (pages are the DMA
    unit — the block table gathers whole pages).
    """
    batch: int
    hkv: int                 # KV heads (grid parallelism, with batch)
    group: int               # Hq // Hkv (merge-pass rows = batch·hkv·group)
    kv_len: int              # cache length the plan is tuned for
    head_dim: int
    page_size: int = 0
    dtype_bytes: int = BF16

    def key(self) -> str:
        """Stable string form used as the JSON cache key."""
        return (f"b{self.batch}.h{self.hkv}.g{self.group}.s{self.kv_len}"
                f".d{self.head_dim}.p{self.page_size}.by{self.dtype_bytes}")


@dataclasses.dataclass(frozen=True)
class LaunchPlan:
    """A chosen (num_splits, block_kv) with its predicted/measured time."""
    num_splits: int
    block_kv: int
    time_s: float            # cost-model prediction, or sweep measurement
    source: str = "model"    # "model" | "sweep" | "cache"


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def predict_time(shape: DecodeShape, num_splits: int, block_kv: int, *,
                 parallelism: int = DEFAULT_PARALLELISM,
                 hbm_bw: float = HBM_BW) -> float:
    """Cost-model seconds for one decode launch at the given parameters.

    Occupancy vs. merge overhead (module docstring): each of the
    ``B·Hkv·num_splits`` grid cells streams its KV slice once; cells beyond
    the hardware's concurrent capacity serialize into waves; splitting adds
    one O(ns·B·Hq·(D+2)) f32 pass to merge the partial states.
    """
    nk = max(1, _ceil_div(shape.kv_len, block_kv))
    num_splits = max(1, min(num_splits, nk))
    blocks_per_split = _ceil_div(nk, num_splits)
    kv_bytes_per_cell = (2 * blocks_per_split * block_kv * shape.head_dim
                         * shape.dtype_bytes)
    cells = shape.batch * shape.hkv * num_splits
    waves = _ceil_div(cells, parallelism)
    t_attn = waves * (kv_bytes_per_cell / hbm_bw + GRID_CELL_OVERHEAD_S)
    if num_splits == 1:
        return t_attn
    hq = shape.hkv * shape.group
    merge_bytes = num_splits * shape.batch * hq * (shape.head_dim + 2) * 4
    # the merge reads every partial and writes one final state (≈2× traffic)
    t_merge = 2 * merge_bytes / hbm_bw + MERGE_OVERHEAD_S
    return t_attn + t_merge


def candidate_plans(shape: DecodeShape) -> Sequence[Tuple[int, int]]:
    """(num_splits, block_kv) pairs worth considering for a shape.

    Paged caches fix ``block_kv = page_size``; contiguous caches sweep the
    8-row-aligned block candidates no larger than the cache. Split counts are
    capped so every split owns at least one KV block.
    """
    if shape.page_size > 0:
        blocks = (shape.page_size,)
    else:
        blocks = tuple(b for b in BLOCK_KV_CANDIDATES if b <= shape.kv_len)
        if not blocks:
            blocks = (max(8, _ceil_div(shape.kv_len, 8) * 8),)
    pairs = []
    for bk in blocks:
        nk = max(1, _ceil_div(shape.kv_len, bk))
        for ns in SPLIT_CANDIDATES:
            if ns <= nk:
                pairs.append((ns, bk))
    return pairs


def plan_decode(shape: DecodeShape, *,
                sweep: Optional[Callable[[int, int], float]] = None,
                cache: Optional["AutotuneCache"] = None,
                parallelism: int = DEFAULT_PARALLELISM) -> LaunchPlan:
    """Choose launch parameters for one decode geometry.

    Pure by default — the cost model alone ranks :func:`candidate_plans`, so
    a valid plan never needs a device. ``sweep`` is an optional measured
    refinement: a callable ``(num_splits, block_kv) -> seconds`` (e.g. a
    wall-clock timer over the real kernel — ``benchmarks/decode_split.py``
    builds one) applied to the model's top candidates. ``cache`` memoises
    per :meth:`DecodeShape.key`; hits skip both model and sweep.
    """
    if cache is not None:
        hit = cache.get(shape)
        if hit is not None:
            return hit
    ranked = sorted(candidate_plans(shape),
                    key=lambda p: predict_time(shape, *p,
                                               parallelism=parallelism))
    ns, bk = ranked[0]
    plan = LaunchPlan(num_splits=ns, block_kv=bk,
                      time_s=predict_time(shape, ns, bk,
                                          parallelism=parallelism))
    if sweep is not None:
        best = None
        for ns, bk in ranked[:4]:          # measure only the model's top-4
            t = sweep(ns, bk)
            if best is None or t < best.time_s:
                best = LaunchPlan(num_splits=ns, block_kv=bk, time_s=t,
                                  source="sweep")
        plan = best
    if cache is not None:
        cache.put(shape, plan)
    return plan


def plan_decode_persistent(shape: DecodeShape, **kw) -> LaunchPlan:
    """:func:`plan_decode` through the default persistent cache.

    Owns the cache lifecycle for callers that just want a plan: open the
    default cache, plan (hits short-circuit), persist — swallowing OSError so
    read-only cache locations degrade to planning without memoisation. The
    one entry point the serving engine and launcher share.
    """
    cache = AutotuneCache()
    plan = plan_decode(shape, cache=cache, **kw)
    try:
        cache.save()
    except OSError:
        pass                       # read-only filesystems: plan still valid
    return plan


class AutotuneCache:
    """Persistent JSON store of launch plans, keyed by device kind and
    decode geometry.

    Resolution order for the backing file: explicit ``path`` argument >
    ``$REPRO_AUTOTUNE_CACHE`` > ``~/.cache/repro/autotune.json`` > a
    repo-local ``.autotune_cache.json`` (when no home is writable). Writes
    are atomic (tempfile + rename) so concurrent engines can share a cache.
    Plans of every device kind share the file; each is read back only on
    its own kind (``jax.devices()[0].device_kind``).
    """

    def __init__(self, path: Optional[os.PathLike] = None):
        import jax
        self.path = Path(path) if path is not None else self.default_path()
        self.device_kind = jax.devices()[0].device_kind
        self._plans: Dict[str, LaunchPlan] = {}
        self.load()

    def _key(self, shape: DecodeShape) -> str:
        return f"{self.device_kind}/{shape.key()}"

    @staticmethod
    def default_path() -> Path:
        """The environment-overridable cache location (class docstring)."""
        env = os.environ.get(CACHE_ENV)
        if env:
            return Path(env)
        try:
            home = Path.home()
        except RuntimeError:
            home = None
        if home is not None:
            return home / ".cache" / "repro" / "autotune.json"
        return Path(".autotune_cache.json")

    def load(self) -> None:
        """Re-read the backing file (missing/corrupt files load as empty)."""
        self._plans = {}
        try:
            raw = json.loads(self.path.read_text())
        except (OSError, ValueError):
            return
        for key, rec in raw.items():
            try:
                self._plans[key] = LaunchPlan(
                    num_splits=int(rec["num_splits"]),
                    block_kv=int(rec["block_kv"]),
                    time_s=float(rec["time_s"]),
                    source="cache")
            except (KeyError, TypeError, ValueError):
                continue                   # skip malformed entries, keep rest

    def get(self, shape: DecodeShape) -> Optional[LaunchPlan]:
        """Cached plan for this exact geometry on this device kind, or None."""
        return self._plans.get(self._key(shape))

    def put(self, shape: DecodeShape, plan: LaunchPlan) -> None:
        """Record a plan in memory (call :meth:`save` to persist)."""
        self._plans[self._key(shape)] = plan

    def save(self) -> None:
        """Atomically persist every recorded plan to the backing file."""
        self.path.parent.mkdir(parents=True, exist_ok=True)
        payload = {k: {"num_splits": p.num_splits, "block_kv": p.block_kv,
                       "time_s": p.time_s, "source": p.source}
                   for k, p in self._plans.items()}
        fd, tmp = tempfile.mkstemp(dir=str(self.path.parent),
                                   prefix=self.path.name, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
