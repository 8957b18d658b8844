"""Index-dtype policy: one place that decides int32 vs int64.

The repo runs JAX in default x32 mode, where every silent
``jnp.asarray(..., int64)`` downcast and every int32 cumsum past
2³¹−1 wraps negative without a word — at Graph500 scale 26 the CSR
slot count (32·n = 2³¹) crosses exactly that line.  Every
index-carrying array construction routes its dtype choice through
:func:`index_dtype` so the decision is auditable (the bounds pass
evaluates the same policy on synthetic scales) and the failure mode is
a loud :class:`IndexWidthError` at build time, never a wrapped offset
at count time.
"""
from __future__ import annotations

import numpy as np

#: Largest value an int32 index can address.
INT32_MAX = 2**31 - 1

#: Largest value an int64 index can address.
INT64_MAX = 2**63 - 1


class IndexWidthError(OverflowError):
    """An index bound needs a wider dtype than the runtime provides."""


def index_dtype(bound: int) -> np.dtype:
    """Smallest of int32/int64 that exactly represents every index in
    ``[0, bound]``.  ``bound`` is inclusive: an array of ``k`` slots
    whose offsets may equal ``k`` (CSR row offsets do) must pass
    ``bound=k``, not ``k - 1``."""
    bound = int(bound)
    if bound < 0:
        raise ValueError(f"index bound must be >= 0; got {bound}")
    if bound <= INT32_MAX:
        return np.dtype(np.int32)
    if bound <= INT64_MAX:
        return np.dtype(np.int64)
    raise IndexWidthError(
        f"index bound {bound} exceeds int64; no supported index dtype"
    )


def jnp_index_dtype(bound: int, *, site: str) -> np.dtype:
    """:func:`index_dtype` for arrays that will cross onto a device.

    Under default x32 mode jax silently *downcasts* int64 arrays to
    int32 — the exact silent wrap this policy exists to prevent — so a
    bound that needs int64 raises :class:`IndexWidthError` naming the
    call site unless x64 is enabled (``jax.enable_x64(True)``
    or the ``jax_enable_x64`` config flag)."""
    dt = index_dtype(bound)
    if dt == np.dtype(np.int64):
        import jax

        if not jax.config.jax_enable_x64:
            raise IndexWidthError(
                f"{site}: indices up to {bound} need int64, but jax "
                f"x64 mode is disabled — enable jax_enable_x64 (or "
                f"shard the input below 2**31 slots per host)"
            )
    return dt
