"""Pallas TPU helpers shared by every kernel in this package.

:func:`pallas_compiler_params` builds the Mosaic compiler params (dimension
semantics plus an optional scoped-VMEM limit) for ``pallas_call``.
``auto_interpret`` lives here too: every kernel entry point defaults to
``interpret=True`` off-TPU so the same call sites are CPU-testable.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "pallas_compiler_params",
    "auto_interpret",
    "resolve_interpret",
    "round_up",
]


def round_up(n: int, m: int) -> int:
    """n rounded up to the next multiple of m — THE tile-staircase helper.

    Every pad-to-tile decision (capacity → block_c, F → block_f, ragged T →
    block_t, and the analytic sweep modelling them) must share this one
    definition or the sweep's model silently desynchronizes from the real
    padding.
    """
    return -(-n // m) * m


def pallas_compiler_params(dimension_semantics, *,
                           vmem_limit_bytes: int | None = None):
    """Compiler params carrying ``dimension_semantics`` for ``pallas_call``.

    ``vmem_limit_bytes`` raises the scoped-VMEM budget Mosaic may use for
    the kernel's pipelined blocks (``None`` keeps the compiler default)."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=vmem_limit_bytes,
    )


def auto_interpret() -> bool:
    """True when kernels should run in interpret mode (any non-TPU backend)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Apply the per-backend default when the caller didn't pin a mode."""
    return auto_interpret() if interpret is None else bool(interpret)
