"""Pallas TPU kernel: MoE router — softmax + iterative top-k + renorm.

One pass over a (block_t, E) tile of router logits held in VMEM: numerically
stable softmax, then k rounds of masked argmax (k ≤ 8 everywhere in the
assigned archs, E ≤ 128 — the full expert row fits a single VREG lane tile),
then gate renormalization. Fusing these avoids three HBM round-trips of the
(T, E) probability matrix that the unfused jnp version pays.

**Fused aux statistics** (``with_stats=True``): the same pass also reduces
the per-expert softmax-probability sums and top-k selection counts that the
Switch-style load-balance loss needs — ``mean_probs = probs_sum / T`` and
``density = counts / T`` — so the caller never re-materializes the (T, E)
probability matrix just for the aux loss. Padding rows (ragged T rounded up
to ``block_t``) are masked out of both reductions by the static row bound,
making the sums exact. Each grid step writes its (1, 1, E) partial into a
(num_blocks, 1, E) output; the wrapper reduces over blocks, and the shard_map
caller (``kernels.sharded``) reduces the per-data-shard partials the same
way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .compat import pallas_compiler_params, round_up

__all__ = ["topk_router_pallas"]


def _softmax_topk(logits, k: int):
    """(T, E) f32 logits → probs, renormed top-k gates (T, k), ids (T, k),
    and the (T, E) mask of the selected experts."""
    T, E = logits.shape
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)

    eidx = jax.lax.broadcasted_iota(jnp.int32, (T, E), 1)
    # column j of the outputs is written by a lane-iota select: Mosaic has
    # no scatter, so ``.at[:, j].set`` cannot lower
    kidx = jax.lax.broadcasted_iota(jnp.int32, (T, k), 1)
    work = probs
    gates = jnp.zeros((T, k), jnp.float32)
    ids = jnp.zeros((T, k), jnp.int32)
    for j in range(k):  # k is small and static: unrolled selection
        best = jnp.max(work, axis=-1, keepdims=True)  # (T, 1)
        # lowest expert id among ties (matches lax.top_k tie-breaking)
        best_id = jnp.min(
            jnp.where(work >= best, eidx, E), axis=-1, keepdims=True
        )
        gates = jnp.where(kidx == j, best, gates)
        ids = jnp.where(kidx == j, best_id, ids)
        work = jnp.where(eidx == best_id, -jnp.inf, work)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # probs are ≥ 0, so exactly the selected experts were knocked to -inf
    return probs, gates, ids, work == -jnp.inf


def _router_kernel(logits_ref, gates_ref, ids_ref, *, k: int):
    logits = logits_ref[...].astype(jnp.float32)  # (block_t, E)
    _, gates, ids, _ = _softmax_topk(logits, k)
    gates_ref[...] = gates
    ids_ref[...] = ids


def _router_stats_kernel(
    logits_ref, gates_ref, ids_ref, psum_ref, cnt_ref, *,
    k: int, block_t: int, t_valid: int,
):
    pid = pl.program_id(0)
    logits = logits_ref[...].astype(jnp.float32)  # (block_t, E)
    T, E = logits.shape
    probs, gates, ids, chosen = _softmax_topk(logits, k)
    gates_ref[...] = gates
    ids_ref[...] = ids
    # mask padding rows (global row ≥ t_valid) out of the reductions: the
    # pad rows are zero logits → uniform 1/E probs that would bias the sums
    row = pid * block_t + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
    valid = row < t_valid  # (T, 1)
    psum_ref[...] = jnp.sum(
        jnp.where(valid, probs, 0.0), axis=0, keepdims=True
    )[None]
    cnt_ref[...] = jnp.sum(
        jnp.where(valid & chosen, 1, 0), axis=0, keepdims=True
    ).astype(jnp.int32)[None]


@functools.partial(
    jax.jit, static_argnames=("k", "block_t", "interpret", "with_stats")
)
def topk_router_pallas(logits, k: int, *, block_t: int = 256,
                       interpret: bool = False, with_stats: bool = False):
    """logits (T, E) → (gates (T, k) f32, ids (T, k) i32).

    With ``with_stats=True`` also returns ``probs_sum`` (E,) f32 — the
    per-expert sum of softmax probabilities over the T valid rows — and
    ``counts`` (E,) i32 — the per-expert top-k selection counts; both feed
    the load-balance aux loss without a second (T, E) softmax pass.

    Ragged T is padded up to a ``block_t`` multiple and the outputs sliced
    back — rows are independent, so the pad rows (zeros) never leak (the
    stats reductions mask them explicitly). The old behaviour (silently
    growing the block to the full T) put the whole ragged batch in one VMEM
    tile, which blows VMEM for large T.
    """
    T, E = logits.shape
    block_t = min(block_t, max(T, 1))
    T_pad = round_up(T, block_t)
    padded = logits
    if T_pad != T:
        padded = jnp.pad(logits, ((0, T_pad - T), (0, 0)))
    n_blocks = T_pad // block_t
    grid = (n_blocks,)
    row_specs = [
        pl.BlockSpec((block_t, k), lambda t: (t, 0)),
        pl.BlockSpec((block_t, k), lambda t: (t, 0)),
    ]
    row_shapes = [
        jax.ShapeDtypeStruct((T_pad, k), jnp.float32),
        jax.ShapeDtypeStruct((T_pad, k), jnp.int32),
    ]
    if not with_stats:
        gates, ids = pl.pallas_call(
            functools.partial(_router_kernel, k=k),
            grid=grid,
            in_specs=[pl.BlockSpec((block_t, E), lambda t: (t, 0))],
            out_specs=row_specs,
            out_shape=row_shapes,
            compiler_params=pallas_compiler_params(("parallel",)),
            interpret=interpret,
        )(padded)
        return gates[:T], ids[:T]
    gates, ids, psum, cnt = pl.pallas_call(
        functools.partial(
            _router_stats_kernel, k=k, block_t=block_t, t_valid=T
        ),
        grid=grid,
        in_specs=[pl.BlockSpec((block_t, E), lambda t: (t, 0))],
        # per-block partials are (1, 1, E) blocks of an (n_blocks, 1, E)
        # array: a block's last two dims must equal the array's (or tile
        # by (8, 128)), which a (1, E) block of an (n_blocks, E) array
        # breaks as soon as there is more than one row block
        out_specs=row_specs + [
            pl.BlockSpec((1, 1, E), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, 1, E), lambda t: (t, 0, 0)),
        ],
        out_shape=row_shapes + [
            jax.ShapeDtypeStruct((n_blocks, 1, E), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, 1, E), jnp.int32),
        ],
        compiler_params=pallas_compiler_params(("parallel",)),
        interpret=interpret,
    )(padded)
    return gates[:T], ids[:T], psum.sum(axis=(0, 1)), cnt.sum(axis=(0, 1))
