"""Kernel-layer contracts: the fold, the launch helper, kernel names, f32
state, NEG_INF.

Five rules guarding the invariants the attention kernels were burned by:

* ``no-inline-softmax-fold`` — the online-softmax fold exists exactly twice
  (``kernels/common.py::online_fold`` in-kernel, ``core/online_softmax.py``
  as pure arrays). The seed shipped three near-copies, one silently missing
  the fully-masked-row ``m == NEG_INF`` guard; any new ``jnp.exp(s - …)``
  must route through the shared fold or carry a justified suppression.
* ``mosaic-kwargs-launch`` — every ``pl.pallas_call`` takes its compiler
  params via ``common.mosaic_kwargs``; inline ``CompilerParams`` boilerplate
  is how the interpret-mode switch drifted between wrappers pre-PR 5.
* ``pallas-call-named`` — every ``pl.pallas_call`` passes ``name=``, the name
  its ``tpu_custom_call`` carries in the compiled program and the profiler's
  trace; without it the kernel is named after the caller's transforms
  (``jvp__``, ``transpose_jvp___``) and a trace can find it only by its
  operand types.
* ``f32-accumulators`` — kernel scratch holding ``(acc, m, l)`` state stays
  ``float32``; a bf16 scratch or an accumulator downcast loses exactly the
  bits the online rescale algebra (paper Eq. 2/3) depends on.
* ``shared-mask-constant`` — ``NEG_INF`` is defined once in
  ``core/online_softmax.py`` (a large *finite* negative so ``exp`` stays
  NaN-free on every path); local ``-1e9``/``-inf`` variants break the
  ``m == NEG_INF`` sentinel comparisons that gate fully-masked rows.
"""

from __future__ import annotations

import ast
from typing import List

from tools.analysis.core import (Finding, call_name, dotted,
                                 enclosing_functions, rule)

#: function defs allowed to spell the fold inline: the two canonical homes
FOLD_HOMES = {
    ("src/repro/kernels/common.py", "online_fold"),
    ("src/repro/core/online_softmax.py", None),     # whole module exempt
}

#: names an exp(<name> - …) is treated as a score tile (the fold's input)
SCORE_NAMES = {"s", "scores"}


@rule("no-inline-softmax-fold",
      description="in-kernel exp(s - m) folds must route through "
                  "kernels/common.py::online_fold (the masked-row-guard "
                  "bug class)",
      paths=("src/repro/kernels/*.py", "src/repro/core/*.py"))
def no_inline_softmax_fold(cache, sf) -> List[Finding]:
    """Flag ``jnp.exp(s - …)`` outside the two canonical fold homes."""
    if (sf.rel, None) in FOLD_HOMES:
        return []
    owners = enclosing_functions(sf.tree)
    out = []
    for node in ast.walk(sf.tree):
        if not (isinstance(node, ast.Call)
                and call_name(node) in ("jnp.exp", "np.exp")
                and node.args):
            continue
        arg = node.args[0]
        if not (isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Sub)):
            continue
        left = arg.left
        if not (isinstance(left, ast.Name) and left.id in SCORE_NAMES):
            continue
        fn = owners.get(node)
        if fn is not None and (sf.rel, fn.name) in FOLD_HOMES:
            continue
        out.append(Finding(
            "no-inline-softmax-fold", sf.rel, node.lineno,
            "exp(s - …) outside online_fold/online_softmax — route the "
            "fold through kernels/common.py::online_fold (it carries the "
            "fully-masked-row m == NEG_INF guard)"))
    return out


def _mosaic_bound_names(tree: ast.Module) -> set:
    """Names anywhere in the module bound to a ``mosaic_kwargs(...)`` call."""
    names = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and (call_name(node.value) or "").endswith("mosaic_kwargs")):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    names.add(tgt.id)
    return names


@rule("mosaic-kwargs-launch",
      description="every pl.pallas_call takes compiler params via "
                  "common.mosaic_kwargs, never inline",
      paths=("src/repro/**/*.py",))
def mosaic_kwargs_launch(cache, sf) -> List[Finding]:
    """Flag pallas_call with inline compiler_params / without the helper."""
    bound = _mosaic_bound_names(sf.tree)
    out = []
    for node in ast.walk(sf.tree):
        if not (isinstance(node, ast.Call)
                and (call_name(node) or "").endswith("pallas_call")):
            continue
        has_helper = False
        for kw in node.keywords:
            if kw.arg == "compiler_params":
                out.append(Finding(
                    "mosaic-kwargs-launch", sf.rel, node.lineno,
                    "inline compiler_params= on pallas_call — use "
                    "kernels/common.py::mosaic_kwargs"))
            if kw.arg is None:      # **splat
                v = kw.value
                if (isinstance(v, ast.Call)
                        and (call_name(v) or "").endswith("mosaic_kwargs")):
                    has_helper = True
                elif isinstance(v, ast.Name) and v.id in bound:
                    has_helper = True
        if not has_helper:
            out.append(Finding(
                "mosaic-kwargs-launch", sf.rel, node.lineno,
                "pallas_call without **mosaic_kwargs(...) — the launch "
                "boilerplate (CompilerParams/interpret switch) must come "
                "from kernels/common.py::mosaic_kwargs"))
    return out


@rule("pallas-call-named",
      description="every pl.pallas_call passes name= (the kernel's name in "
                  "the compiled program and the profiler's trace)",
      paths=("src/repro/**/*.py",))
def pallas_call_named(cache, sf) -> List[Finding]:
    """Flag a pallas_call without a ``name=`` keyword."""
    out = []
    for node in ast.walk(sf.tree):
        if (isinstance(node, ast.Call)
                and (call_name(node) or "").endswith("pallas_call")
                and not any(kw.arg == "name" for kw in node.keywords)):
            out.append(Finding(
                "pallas-call-named", sf.rel, node.lineno,
                "pallas_call without name= — the trace and the compile "
                "tests know a kernel by this name"))
    return out


#: scratch-state reference names whose stores must stay f32
ACC_REFS = {"acc_ref", "m_ref", "l_ref"}
DOWNCAST_DTYPES = {"jnp.float16", "jnp.bfloat16", "jnp.int8", "jnp.float8_e4m3fn",
                   "jnp.float8_e5m2", "np.float16"}


@rule("f32-accumulators",
      description="kernel scratch and (acc, m, l) accumulator state stay "
                  "float32 — no downcasts",
      paths=("src/repro/kernels/*.py",))
def f32_accumulators(cache, sf) -> List[Finding]:
    """Flag non-f32 VMEM scratch and sub-f32 astype on (acc, m, l) stores."""
    out = []
    for node in ast.walk(sf.tree):
        # pltpu.VMEM(shape, dtype): scratch carrying the online state is f32
        if (isinstance(node, ast.Call)
                and (call_name(node) or "").endswith("VMEM")
                and len(node.args) >= 2):
            dt = dotted(node.args[1])
            if dt is not None and dt not in ("jnp.float32", "np.float32"):
                out.append(Finding(
                    "f32-accumulators", sf.rel, node.lineno,
                    f"VMEM scratch declared {dt} — online-softmax state "
                    f"scratch must be jnp.float32"))
        # acc_ref[...] = <expr containing .astype(<sub-f32>)>
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if (isinstance(tgt, ast.Subscript)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id in ACC_REFS):
                    for sub in ast.walk(node.value):
                        if (isinstance(sub, ast.Call)
                                and isinstance(sub.func, ast.Attribute)
                                and sub.func.attr == "astype"
                                and sub.args
                                and dotted(sub.args[0]) in DOWNCAST_DTYPES):
                            out.append(Finding(
                                "f32-accumulators", sf.rel, sub.lineno,
                                f"{tgt.value.id} store downcasts via "
                                f".astype({dotted(sub.args[0])}) — the "
                                f"(acc, m, l) state must stay float32"))
    return out


#: |value| at or beyond this is a masking constant, not arithmetic
MASK_MAGNITUDE = 1e9


@rule("shared-mask-constant",
      description="no local -1e9/-inf style mask constants — import "
                  "NEG_INF from core.online_softmax",
      paths=("src/**/*.py", "tools/**/*.py"))
def shared_mask_constant(cache, sf) -> List[Finding]:
    """Flag large-negative literals and -inf spellings outside the source."""
    if sf.rel == "src/repro/core/online_softmax.py":
        return []       # the one definition site
    out = []
    for node in ast.walk(sf.tree):
        bad = None
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            opnd = node.operand
            if (isinstance(opnd, ast.Constant)
                    and isinstance(opnd.value, (int, float))
                    and abs(opnd.value) >= MASK_MAGNITUDE):
                bad = f"-{opnd.value:g}"
            elif dotted(opnd) in ("jnp.inf", "np.inf", "math.inf"):
                bad = f"-{dotted(opnd)}"
        elif (isinstance(node, ast.Call)
              and dotted(node.func) in ("float", "jnp.float32", "np.float32")
              and node.args and isinstance(node.args[0], ast.Constant)
              and str(node.args[0].value).lstrip().startswith("-inf")):
            bad = "float('-inf')"
        elif dotted(node) in ("np.NINF", "numpy.NINF"):
            bad = dotted(node)
        if bad is not None:
            out.append(Finding(
                "shared-mask-constant", sf.rel, node.lineno,
                f"local mask constant {bad} — import NEG_INF from "
                f"repro.core.online_softmax (finite sentinel the masked-row "
                f"guards compare against)"))
    return out
