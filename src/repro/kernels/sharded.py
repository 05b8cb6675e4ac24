"""Per-shard entry points: the fused MoE kernels under ``shard_map``.

The staged dispatch plane (``repro.models.dispatch``) keeps the sort-based
scatter/gather in plain GSPMD-partitioned jnp; only the two compute
hot-spots cross into manual-SPMD land here, so each device runs the fused
Pallas kernel on exactly its local shard:

* ``moe_ffn_sharded`` — the grouped expert FFN on the per-device
  ``(E_v/16, C, D)`` weight + buffer shards of the (data, model) mesh.
* ``topk_router_sharded`` — softmax + top-k + fused aux stats on the
  per-data-shard ``(Ng, E)`` logits slice (router weights are replicated
  over ``model``, so only the data axis is mapped).

Spec arguments come from :meth:`ShardingPolicy.moe_shard_spec`: ``data_spec``
is the mesh axis (or axes tuple) the leading group dim shards over — or
``None`` to replicate, e.g. when the batch collapsed to one dispatch group —
and ``expert_spec`` is the model axis for the E_v dim, or ``None`` when E_v
doesn't divide the model-axis extent (every device then redundantly computes
all experts, correct but unsharded, with the caller warning once).

``mesh=None`` short-circuits to the direct single-device kernel calls, so
host smoke tests and the mesh path share one call site. ``check_vma=False``
throughout: ``pallas_call`` carries no replication rule.

Both entry points are **differentiable**: the Pallas kernel runs the
forward, and a ``custom_vjp`` supplies the backward as plain GSPMD jnp
einsum math (recomputing the hidden activations, remat-style) — the same
gradients the einsum reference path produces. Without this,
``pl.program_id`` aborts the JVP trace and the pallas backend couldn't
train; with it, the train step differentiates through the per-shard kernels
on any mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .compat import round_up as _round_up
from .moe_gemm import SKINNY_BLOCK_C, moe_ffn_pallas
from .topk_router import topk_router_pallas

__all__ = ["moe_ffn_sharded", "topk_router_sharded", "effective_block_c"]


def effective_block_c(block_c: int, C: int) -> int:
    """Per-call row-tile clamp shared by the kernel call site, the autotune
    sweep (``benchmarks/roofline.py``), and its pinning test.

    The configured ``block_c`` clamps down to the capacity's staircase so a
    single configured tile serves every shape: ``round_up(C, 8)`` keeps the
    f32 sublane tile for train/prefill capacities, and capacities at or
    below :data:`~repro.kernels.moe_gemm.SKINNY_BLOCK_C` take the skinny
    decode tile instead — decode's C≈4 would otherwise pad its row dim
    100% against the 8-row floor."""
    floor = SKINNY_BLOCK_C if C <= SKINNY_BLOCK_C else 8
    return min(block_c, _round_up(C, floor))


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def moe_ffn_sharded(
    x_e, w_gate, w_up, w_down, *, mesh, data_spec, expert_spec,
    block_c: int = 128, block_f: int = 256, interpret: bool = False,
    pad_expert_to: int | None = None, layer=None,
):
    """(Gd, E_v, C, D) expert buffers → (Gd, E_v, C, D) FFN outputs.

    Capacity rounds up to a ``block_c`` multiple — the pad rows are zeros
    (they gather the zero pad token), FFN(0) = 0, and the rows are sliced
    back off; that rounding is the §3.3.2 tile staircase the paper profiles.
    F pads with zero columns/rows, exact for silu(x@Wg)·(x@Wu)@Wd.

    ``pad_expert_to`` (from :meth:`ShardingPolicy.moe_expert_pad`) handles
    E_v that doesn't divide the model axis: the expert dim of the buffers
    *and* weights pads with zero rows — dead slots whose FFN output is
    exactly zero — up to the axis multiple, ``expert_spec`` shards the
    padded dim, and the dead rows are sliced back off. Every device then
    computes only its shard instead of redundantly holding all experts.

    With a mesh, the kernel runs inside ``shard_map``: each device sees its
    local (Gd/data, E_v/model, C_pad, D) buffer shard and (E_v/model, D, F)
    weight shards and loops its (static, usually 1) local data groups.
    Without one, the same per-group loop runs directly.

    A 5-D ``x_e`` carries a stacked leading layer dim: (L, Gd, E_v, C, D)
    buffers with (L, E_v, D, F) weights scan the per-layer call over L —
    the whole-stack entry the scan-fused decode executable composes with.

    With ``layer`` (an int32 scalar) the weights are the whole stack,
    (L, E_v, D, F) / (L, E_v, F, D), and the kernel reads that layer's in
    place (:func:`~repro.kernels.moe_gemm.moe_ffn_pallas`): a layer scan
    passes its stacked weights without slicing one layer's out. Where the
    weights need padding, only that layer is sliced out and padded. This
    entry is forward only (the decode step's): it has no VJP.
    """
    if x_e.ndim == 5:
        def layer_call(_, xs):
            xl, wg, wu, wd = xs
            return None, moe_ffn_sharded(
                xl, wg, wu, wd, mesh=mesh, data_spec=data_spec,
                expert_spec=expert_spec, block_c=block_c, block_f=block_f,
                interpret=interpret, pad_expert_to=pad_expert_to,
            )
        _, y = jax.lax.scan(layer_call, None, (x_e, w_gate, w_up, w_down))
        return y
    Gd, Ev, C, D = x_e.shape
    F = w_gate.shape[-1]
    Ev_real = Ev
    bf = min(block_f, _round_up(F, 128))
    Fp = _round_up(F, bf)
    if layer is not None and (Fp != F or (pad_expert_to or 0) > Ev):
        # padding the stack would copy every layer's weights
        w_gate, w_up, w_down = (w[layer] for w in (w_gate, w_up, w_down))
        layer = None
    if pad_expert_to is not None and pad_expert_to > Ev:
        ep = pad_expert_to - Ev
        x_e = jnp.pad(x_e, ((0, 0), (0, ep), (0, 0), (0, 0)))
        w_gate = jnp.pad(w_gate, ((0, ep), (0, 0), (0, 0)))
        w_up = jnp.pad(w_up, ((0, ep), (0, 0), (0, 0)))
        w_down = jnp.pad(w_down, ((0, ep), (0, 0), (0, 0)))
        Ev = pad_expert_to
    bc = effective_block_c(block_c, C)
    Cp = _round_up(C, bc)
    if Cp != C:
        x_e = jnp.pad(x_e, ((0, 0), (0, 0), (0, Cp - C), (0, 0)))
    if Fp != F:
        w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, Fp - F)))
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, Fp - F)))
        w_down = jnp.pad(w_down, ((0, 0), (0, Fp - F), (0, 0)))

    def per_group(xl, wg, wu, wd, *at):
        # xl (g_local, e_local, Cp, D): static local group count, ≥ 1;
        # at: the layer index of stacked weights, or nothing
        y = jnp.stack([
            moe_ffn_pallas(
                xl[g], wg, wu, wd, *at, block_c=bc, block_f=bf,
                interpret=interpret,
            )
            for g in range(xl.shape[0])
        ])
        return y.astype(xl.dtype)

    if mesh is None:
        kernel_fwd = per_group
    else:
        stacked = layer is not None
        w_spec = P(*(None,) * stacked, expert_spec, None, None)
        kernel_fwd = _shard_map(
            per_group, mesh,
            in_specs=(P(data_spec, expert_spec, None, None),
                      w_spec, w_spec, w_spec, *(P(),) * stacked),
            out_specs=P(data_spec, expert_spec, None, None),
        )

    if layer is not None:
        y = kernel_fwd(x_e, w_gate, w_up, w_down, layer)
        return y[:, :Ev_real, :C, :]

    @jax.custom_vjp
    def call(xp, wg, wu, wd):
        return kernel_fwd(xp, wg, wu, wd)

    def call_fwd(xp, wg, wu, wd):
        return kernel_fwd(xp, wg, wu, wd), (xp, wg, wu, wd)

    def call_bwd(res, g):
        # reference math of y = (silu(x@Wg) · (x@Wu)) @ Wd, recomputing the
        # hidden activations (remat-style); plain jnp → GSPMD-partitioned
        xp, wg, wu, wd = res
        xf = xp.astype(jnp.float32)
        h1 = jnp.einsum("gecd,edf->gecf", xf, wg.astype(jnp.float32))
        h2 = jnp.einsum("gecd,edf->gecf", xf, wu.astype(jnp.float32))
        sig = jax.nn.sigmoid(h1)
        s = h1 * sig  # silu
        gf = g.astype(jnp.float32)
        dh = jnp.einsum("gecd,efd->gecf", gf, wd.astype(jnp.float32))
        dwd = jnp.einsum("gecf,gecd->efd", s * h2, gf)
        dh2 = dh * s
        dh1 = dh * h2 * (sig * (1.0 + h1 * (1.0 - sig)))  # silu'
        dx = (
            jnp.einsum("gecf,edf->gecd", dh1, wg.astype(jnp.float32))
            + jnp.einsum("gecf,edf->gecd", dh2, wu.astype(jnp.float32))
        )
        dwg = jnp.einsum("gecd,gecf->edf", xf, dh1)
        dwu = jnp.einsum("gecd,gecf->edf", xf, dh2)
        return (
            dx.astype(xp.dtype), dwg.astype(wg.dtype),
            dwu.astype(wu.dtype), dwd.astype(wd.dtype),
        )

    call.defvjp(call_fwd, call_bwd)
    y = call(x_e, w_gate, w_up, w_down)
    return y[:, :Ev_real, :C, :]


def topk_router_sharded(
    logits, k: int, *, mesh, data_spec, block_t: int = 256,
    interpret: bool = False,
):
    """logits (Gd, Ng, E) → (gates (Gd, Ng, k), ids (Gd, Ng, k),
    probs_sum (E,), counts (E,)).

    Each data shard runs the fused router kernel on its local (Ng, E) slice
    and emits (1, E) partial aux sums; the partials concatenate over the
    mapped group dim and reduce here, so the returned stats are the exact
    global sums either way.
    """
    Gd, Ng, E = logits.shape

    def per_shard(lg):
        gl = lg.shape[0]
        g, i, ps, cnt = topk_router_pallas(
            lg.reshape(gl * Ng, E), k, block_t=block_t,
            interpret=interpret, with_stats=True,
        )
        return (
            g.reshape(gl, Ng, k), i.reshape(gl, Ng, k), ps[None], cnt[None]
        )

    if mesh is None:
        kernel_fwd = per_shard
    else:
        kernel_fwd = _shard_map(
            per_shard, mesh,
            in_specs=(P(data_spec, None, None),),
            out_specs=(P(data_spec, None, None), P(data_spec, None, None),
                       P(data_spec, None), P(data_spec, None)),
        )

    def primal(lg):
        gates, ids, psum, cnt = kernel_fwd(lg)
        # int outputs leave the custom_vjp as f32 (exact: ids < E ≤ 128,
        # counts < 2^24) — integer custom_vjp outputs would carry float0
        # tangents under linearize/remat and break the integer index
        # arithmetic downstream; the f32→i32 cast outside drops tangents
        # symbolically instead
        return (
            gates, ids.astype(jnp.float32), psum.sum(axis=0),
            cnt.sum(axis=0).astype(jnp.float32),
        )

    @jax.custom_vjp
    def call(lg):
        return primal(lg)

    def call_fwd(lg):
        out = primal(lg)
        return out, (lg, out[1].astype(jnp.int32))  # logits + selected ids

    def call_bwd(res, cot):
        # same gradient the einsum reference path produces: softmax →
        # top-k gather → renorm, with the probs_sum cotangent broadcast to
        # every row. ids/counts are integer outputs: their cotangents are
        # symbolic zeros, dropped.
        lg, ids = res
        dgates, _dids, dpsum, _dcnt = cot
        probs = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)  # (Gd,Ng,E)
        pick = jnp.take_along_axis(probs, ids, axis=-1)  # (Gd, Ng, k)
        ssum = jnp.sum(pick, axis=-1, keepdims=True)
        dgates = dgates.astype(jnp.float32)
        # gates = pick / Σpick  ⇒  dpick_i = dgates_i/Σ − (Σ_j dgates_j·pick_j)/Σ²
        dot = jnp.sum(dgates * pick, axis=-1, keepdims=True)
        dpick = dgates / ssum - dot / (ssum * ssum)
        sel = jax.nn.one_hot(ids, probs.shape[-1], dtype=jnp.float32)
        dprobs = jnp.sum(dpick[..., None] * sel, axis=2)  # scatter to (…, E)
        dprobs = dprobs + dpsum.astype(jnp.float32)[None, None, :]
        dlg = probs * (
            dprobs - jnp.sum(dprobs * probs, axis=-1, keepdims=True)
        )
        return (dlg.astype(lg.dtype),)

    call.defvjp(call_fwd, call_bwd)
    gates, ids_f, psum, cnt_f = call(logits)
    return gates, ids_f.astype(jnp.int32), psum, cnt_f.astype(jnp.int32)
