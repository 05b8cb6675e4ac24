"""Pallas TPU kernel: fused grouped expert FFN (gate ∘ up → silu·mul → down).

This is GEM's compute hot-spot: the per-device expert GEMM whose *tile
staircase* is exactly what the paper's Step-2 profiler samples (§3.3.2 —
"latency only jumps upon crossing tile boundaries"). On TPU the tile is the
``block_c`` row block feeding the 128×128 MXU, so the profiler samples token
counts at multiples of ``block_c``.

Layout (matches ``repro.models.moe``'s capacity dispatch): tokens arrive
pre-grouped per (virtual) expert in a dense (E, C, D) buffer; weights are
stacked (E, D, F) / (E, F, D). One kernel invocation computes

    y[e, c, :] = (silu(x[e, c, :] @ Wg[e]) * (x[e, c, :] @ Wu[e])) @ Wd[e]

Grid: (E, C/block_c, F/block_f) — experts and row blocks parallel, the F
axis is the contraction of the second GEMM and accumulates into the output
block (zeroed at the first F step). All operands are tiled into VMEM via
BlockSpecs; accumulation is fp32 in the output ref, cast once at the end.

**Stacked weights.** Given a ``layer`` index, the weights are the whole
stack, (L, E, D, F) / (L, E, F, D), and the kernel reads that layer's
blocks in place: the index is scalar-prefetched into SMEM and the weight
BlockSpecs' index maps pick the layer from it. A layer scan then hands
the kernel the stacked weights as they are, instead of slicing each
layer's weights out into a buffer of their own first (a full extra read
and write of every expert weight per step, which the compiler may also
stage in VMEM ahead of the kernel).

VMEM budget per step (:func:`vmem_bytes`): the pipeline double-buffers
every block — x (block_c·D), Wg, Wu (2·D·block_f), Wd (block_f·D) and the
fp32 output (block_c·D) — plus the fp32 down-projection result and the
(block_c, block_f) hidden activations. Granite's prefill tile (block_c=1024,
D=1536, block_f=128) needs ≈ 30 MB, above Mosaic's default scoped limit
(16 MiB on v5e), so the kernel raises ``vmem_limit_bytes`` to what its blocks need (v5e has
128 MiB of VMEM).

**Skinny decode row tile.** Decode capacities are tiny (C≈4 on decode_32k),
so an 8-row ``block_c`` floor pads the row dim 100%. ``block_c`` may drop to
``SKINNY_BLOCK_C`` (= 4): below the f32 (8, 128) sublane tile Mosaic pads
the *registers* internally, but HBM→VMEM traffic and the FLOPs fed to the
MXU halve — the staircase waste the profiler samples. The sweep in
``benchmarks/roofline.py`` grids this tile and the clamp in
``kernels.sharded.effective_block_c`` applies it exactly when C ≤ 4.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compat import pallas_compiler_params

__all__ = ["moe_ffn_pallas", "SKINNY_BLOCK_C"]

# the skinny decode row tile: the smallest legal block_c. Tiles below the
# f32 sublane minimum (8) are register-padded by Mosaic but still halve the
# row-dim memory traffic at decode's C≈4 capacities.
SKINNY_BLOCK_C = 4

_MiB = 1 << 20
# Mosaic's own scratch rides on top of the blocks and is not counted by
# vmem_bytes: headroom for it, and a floor so small tiles keep a generous
# limit (far inside the 128 MiB of VMEM on v5e)
_VMEM_HEADROOM = 8 * _MiB
_VMEM_FLOOR = 32 * _MiB


def vmem_bytes(block_c: int, block_f: int, D: int, itemsize: int) -> int:
    """VMEM one grid step of :func:`moe_ffn_pallas` holds, in bytes."""
    in_blocks = (block_c * D + 3 * D * block_f) * itemsize
    out_block = block_c * D * 4
    temps = block_c * D * 4 + 3 * block_c * block_f * 4
    return 2 * (in_blocks + out_block) + temps


def _ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    f_idx = pl.program_id(2)

    @pl.when(f_idx == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]  # (block_c, D)
    wg = wg_ref[0]  # (D, block_f)
    wu = wu_ref[0]
    wd = wd_ref[0]  # (block_f, D)
    h_gate = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    h_up = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    h = jax.nn.silu(h_gate) * h_up
    o_ref[...] += jnp.dot(
        h.astype(x.dtype), wd, preferred_element_type=jnp.float32
    )[None]


def _ffn_kernel_at_layer(layer_ref, *refs):
    # the layer index is read only by the BlockSpecs' index maps
    del layer_ref
    _ffn_kernel(*refs)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "interpret")
)
def moe_ffn_pallas(
    x_e, w_gate, w_up, w_down, layer=None, *, block_c: int = 128,
    block_f: int = 256, interpret: bool = False,
):
    """x_e (E, C, D), w_gate/w_up (E, D, F), w_down (E, F, D) → (E, C, D).

    With ``layer`` (an int32 scalar, traced or not) the weights carry a
    leading layer dim, (L, E, D, F) / (L, E, F, D), and layer ``layer``
    is read in place.

    C must divide by ``block_c`` and F by ``block_f`` (the dispatch pads
    capacity to the tile size — that padding IS the latency staircase).
    """
    E, C, D = x_e.shape
    F = w_gate.shape[-1]
    if block_c < SKINNY_BLOCK_C:
        raise ValueError(
            f"block_c={block_c} below the skinny decode tile "
            f"{SKINNY_BLOCK_C}"
        )
    if C % block_c or F % block_f:
        raise ValueError(
            f"C={C} must divide block_c={block_c}, F={F} block_f={block_f}"
        )
    grid = (E, C // block_c, F // block_f)
    compiler_params = pallas_compiler_params(
        ("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(
            vmem_bytes(block_c, block_f, D, x_e.dtype.itemsize)
            + _VMEM_HEADROOM,
            _VMEM_FLOOR,
        ),
    )
    out_shape = jax.ShapeDtypeStruct((E, C, D), jnp.float32)
    if layer is None:
        out = pl.pallas_call(
            _ffn_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_c, D), lambda e, c, f: (e, c, 0)),
                pl.BlockSpec((1, D, block_f), lambda e, c, f: (e, 0, f)),
                pl.BlockSpec((1, D, block_f), lambda e, c, f: (e, 0, f)),
                pl.BlockSpec((1, block_f, D), lambda e, c, f: (e, f, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_c, D), lambda e, c, f: (e, c, 0)),
            out_shape=out_shape,
            compiler_params=compiler_params,
            interpret=interpret,
        )(x_e, w_gate, w_up, w_down)
        return out.astype(x_e.dtype)
    # the layer dim is squeezed out of the weight blocks, so the kernel
    # body sees the same (1, D, block_f) / (1, block_f, D) blocks
    out = pl.pallas_call(
        _ffn_kernel_at_layer,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_c, D), lambda e, c, f, l: (e, c, 0)),
                pl.BlockSpec((None, 1, D, block_f),
                             lambda e, c, f, l: (l[0], e, 0, f)),
                pl.BlockSpec((None, 1, D, block_f),
                             lambda e, c, f, l: (l[0], e, 0, f)),
                pl.BlockSpec((None, 1, block_f, D),
                             lambda e, c, f, l: (l[0], e, f, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_c, D),
                                   lambda e, c, f, l: (e, c, 0)),
        ),
        out_shape=out_shape,
        compiler_params=compiler_params,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), x_e, w_gate, w_up, w_down)
    return out.astype(x_e.dtype)
