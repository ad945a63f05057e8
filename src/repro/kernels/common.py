"""Shared Pallas kernel machinery: the online-softmax fold + launch boilerplate.

Every attention kernel in this package folds blocks of masked scores into the
same VMEM ``(m, l, acc)`` scratch state (paper Eq. 2) — the forward kernel,
the contiguous/paged decode kernels and the split/partial decode kernels used
by distributed serving. The fold used to live as three near-copies (one of
which silently lacked the fully-masked-row ``m == NEG_INF`` guard); this
module is now the single in-kernel counterpart of the pure-array algebra in
``core/online_softmax.py``.

It also owns the ``pallas_call`` launch boilerplate (CompilerParams /
interpret-mode switch) that every kernel wrapper previously re-spelled.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.online_softmax import NEG_INF

LANES = 128  # TPU vector lane width; (rows, LANES) f32 scratch for m/l state
SUBLANES = 8  # TPU vector sublane count: the row tile of a 32-bit block


def mosaic_kwargs(interpret: bool,
                  dimension_semantics: Sequence[str]) -> Dict:
    """``pallas_call`` kwargs for the Mosaic compiler.

    Interpret mode (CPU validation) takes no compiler params; on hardware the
    grid's ``dimension_semantics`` mark which axes may run in parallel and
    which carry scratch state sequentially ("arbitrary"). One helper instead
    of the same four-line conditional in every kernel wrapper.
    """
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics))}


def online_fold(s, v, acc_ref, m_ref, l_ref, *, acc_dtype,
                p_transform: Optional[Callable] = None):
    """Fold one masked score block into the VMEM ``(m, l, acc)`` scratch state.

    The in-kernel form of ``online_softmax.update`` (paper Eq. 2): ``s`` is
    the f32 ``[rows, block]`` score tile with disallowed positions already set
    to ``NEG_INF``; ``v`` is the matching ``[block, D]`` value tile. ``m_ref``
    and ``l_ref`` are ``[rows, LANES]`` f32 scratch (column 0 authoritative),
    ``acc_ref`` is ``[rows, D]`` f32 scratch.

    Rows that have only ever seen masked scores keep ``m == NEG_INF``; there
    ``exp(s - m)`` would be ``exp(0) = 1``, silently counting masked
    positions. The ``m_safe`` substitution zeroes those probabilities so ``l``
    stays 0 and the caller's ``l == 0`` finalize guard emits exact zeros
    (fully-masked rows: packed-batch padding, ``kv_len == 0`` decode rows).

    ``p_transform`` hooks between the ``l`` update and the ``P·V`` matmul —
    the forward kernel applies dropout there (``l`` must see pre-dropout
    probabilities, matching the reference softmax).
    """
    m_prev = m_ref[:, 0]
    l_prev = l_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)                     # rescale of old state
    m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe[:, None])                    # unnormalised probs
    l_ref[...] = jnp.broadcast_to(
        (l_prev * alpha + jnp.sum(p, axis=1))[:, None], l_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    if p_transform is not None:
        p = p_transform(p)
    # P downcast to the value dtype for the MXU (the paper's MMA-C → MMA-A
    # layout transform happens here on Volta; Mosaic owns the VREG relayout)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=acc_dtype)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv.astype(jnp.float32)
