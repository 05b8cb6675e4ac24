"""Mixture-of-Experts layer with GEM placement as a first-class feature.

**Virtual-expert factorization.** Expert weights are stacked as
``(E_v, D, F_v)`` with ``E_v = num_experts × expert_tp`` and
``F_v = expert_d_ff / expert_tp``: each real expert is split into
``expert_tp`` F-slices ("virtual experts"). The virtual-expert dim is sharded
over the 16-wide ``model`` axis, which expresses EP×expert-TP in one mesh
axis with zero padding for any expert count (mixtral 8e×2 → 16/16,
granite 40e×2 → 80/16 = 5 per device). The F-slices of one real expert
produce partial sums that the combine step adds back together, so the
factorization is exact.

**GEM placement.** A placement is a permutation of virtual-expert *slots*:
slot ``s`` (physical row ``s``, living on device ``s // (E_v/16)``) holds
virtual expert ``slot_to_expert[s]``. The router's output is remapped through
``expert_to_slot`` (a gather from an (E_v,) table) and the stacked weights
are permuted once at load time (`apply_placement`). Model outputs are
invariant to the placement (property-tested); what changes is *which device*
the hot experts' tokens land on — exactly the paper's lever.

**Staged dispatch plane.** :func:`moe_layer` is a thin composition of the
four stages in :mod:`repro.models.dispatch` —
``route → build_dispatch → expert_compute → combine`` — each passing small
typed structs (``RouterOutput`` / ``DispatchPlan`` / ``MoEAux``). Dispatch
is sort-based (no (N, E, C) one-hot): assignments are ranked within their
slot via argsort + segment offsets, dropped beyond the static capacity,
gathered into (E_v, C, D) buffers, FFN'd, and combined with a scatter-add.
Per-real-expert token counts are returned for GEM's Step-1 trace collection.

**Backends.** ``ModelConfig.moe_backend`` selects the expert-compute stage;
all three route through the same staged structure:

* ``"einsum"`` (default) — grouped-einsum FFN; fully GSPMD-partitionable,
  the parity reference for the others.
* ``"pallas"`` — router top-k and the grouped expert FFN run through the
  fused Pallas kernels (``topk_router_pallas`` / ``moe_ffn_pallas``). Under
  a device mesh the kernels execute *per shard* inside ``shard_map``: each
  device runs the FFN kernel on its local (E_v/16, C, D) weight and buffer
  shard (the router on its data-axis logits slice), while the sort-based
  scatter/gather stays outside in GSPMD land — no einsum fallback. Capacity
  pads up to the kernel's ``block_c`` row tile — exactly the §3.3.2 latency
  staircase GEM's profiler samples. The router kernel also emits the
  load-balance aux statistics, so no duplicate (T, E) softmax pass runs.
  Off-TPU the kernels run in interpret mode, so both the host path and the
  shard_map path are CPU-testable.
* ``"dense_ref"`` — every expert computed on every token (capacity-free
  oracle); router stats still flow so GEM's Step-1 hooks keep working.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import MOE_BACKENDS, ModelConfig
from ..sharding.policy import ShardingPolicy, host_policy
from .dispatch import (
    MoEAux,
    _warn_once,
    build_dispatch,
    combine,
    dense_mix,
    expert_compute,
    route,
)

__all__ = [
    "init_moe",
    "moe_layer",
    "apply_placement",
    "apply_layer_permutation",
    "identity_placement",
    "moe_layer_dense_ref",
    "resolve_moe_backend",
    "MoEAux",
]


def resolve_moe_backend(
    backend: str | None, config: ModelConfig, policy: ShardingPolicy
) -> str:
    """Effective backend for this call: explicit arg > config."""
    del policy  # kept in the signature for call-site stability
    backend = backend if backend is not None else config.moe_backend
    if backend not in MOE_BACKENDS:
        raise ValueError(f"moe_backend={backend!r} not in {MOE_BACKENDS}")
    return backend


def init_moe(
    key, config: ModelConfig, *, num_layers: int, dtype, policy: ShardingPolicy
):
    D = config.d_model
    E = config.num_experts
    tp = config.expert_tp
    Ev = E * tp
    Fv = config.expert_d_ff // tp
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = float(1.0 / np.sqrt(D))
    s_out = float(1.0 / np.sqrt(config.expert_d_ff))
    params = {
        "router": jax.random.normal(k1, (num_layers, D, E), dtype) * s_in,
        "w_gate": jax.random.normal(k2, (num_layers, Ev, D, Fv), dtype) * s_in,
        "w_up": jax.random.normal(k3, (num_layers, Ev, D, Fv), dtype) * s_in,
        "w_down": jax.random.normal(k4, (num_layers, Ev, Fv, D), dtype) * s_out,
    }
    m = policy.model_axis
    f = "data" if (policy.fsdp and policy.mesh is not None) else None
    specs = {
        "router": policy.spec(None, None, None),
        # ZeRO shards the *non-contraction* dim over data: D for the up/gate
        # projections, D (output) for the down projection — never F_v, or the
        # expert GEMMs turn into per-layer cross-data partial-sum all-reduces
        # of the (E_v, C, D) buffers (measured: 16 GB/layer on granite).
        "w_gate": policy.spec(None, m, f, None),
        "w_up": policy.spec(None, m, f, None),
        "w_down": policy.spec(None, m, None, f),
    }
    return params, specs


def identity_placement(config: ModelConfig, num_layers: int) -> jax.Array:
    """(L, E_v) expert→slot tables for the linear (vLLM-default) layout."""
    Ev = config.num_experts * config.expert_tp
    return jnp.tile(jnp.arange(Ev, dtype=jnp.int32), (num_layers, 1))


def apply_placement(moe_params, slot_to_expert):
    """Permute stacked expert weights into placement order (Step-4, load time).

    ``slot_to_expert``: (L, E_v) int — physical slot s on layer l holds
    virtual expert ``slot_to_expert[l, s]``.
    """
    def permute(w):
        # w: (L, E_v, ...) → take along the expert axis per layer
        return jax.vmap(lambda wl, pl: jnp.take(wl, pl, axis=0))(
            w, slot_to_expert
        )

    out = dict(moe_params)
    for name in ("w_gate", "w_up", "w_down"):
        out[name] = permute(moe_params[name])
    return out


def apply_layer_permutation(
    moe_params,
    layer: int,
    perm,
    *,
    via: str = "host",
    policy: ShardingPolicy | None = None,
    stats_out: list | None = None,
):
    """Apply one layer's row-source map to the stacked expert rows: row
    ``s`` ← old row ``perm[s]`` (online plane's partial placement
    application, applied between decode steps).

    Unlike :func:`apply_placement` this touches a single layer and an
    arbitrary (typically near-identity) source map — the data-plane half of
    a budgeted migration batch; the caller swaps the matching router remap
    table row in the same engine step so weights and routing never disagree.

    ``via`` selects the data plane:

    * ``"host"`` (default) — one parallel row gather per weight array, the
      load-time semantics.
    * ``"collective"`` — the batch lowers to ppermute rounds on the
      expert-sharded rows (:mod:`repro.kernels.collective`), executed under
      the policy's mesh on its model axis; the executed schedule's
      :class:`~repro.kernels.collective.CollectiveStats` (measured
      interconnect traffic) is appended to ``stats_out`` when given. Falls
      back to the host gather — bit-identical, zero measured traffic — when
      the policy has no live expert sharding
      (:meth:`ShardingPolicy.expert_collective_axis`), warning once.
    """
    if via not in ("host", "collective"):
        raise ValueError(f"via={via!r} not in ('host', 'collective')")
    names = ("w_gate", "w_up", "w_down")
    if via == "collective":
        num_slots = int(moe_params[names[0]].shape[1])
        axis = (
            policy.expert_collective_axis(num_slots)
            if policy is not None
            else None
        )
        if axis is None:
            _warn_once(
                ("collective_fallback", num_slots),
                "apply_layer_permutation(via='collective'): no live expert "
                "sharding (mesh absent, 1-wide model axis, or slot count "
                f"{num_slots} not divisible) — falling back to the host row "
                "gather",
            )
        else:
            from ..kernels.collective import apply_row_sources

            arrays = tuple(moe_params[n][layer] for n in names)
            new_arrays, stats = apply_row_sources(
                arrays, perm, mesh=policy.mesh, axis=axis
            )
            if stats_out is not None:
                stats_out.append(stats)
            out = dict(moe_params)
            for name, a in zip(names, new_arrays):
                out[name] = moe_params[name].at[layer].set(a)
            return out
    perm = jnp.asarray(perm, dtype=jnp.int32)
    out = dict(moe_params)
    for name in names:
        w = moe_params[name]
        out[name] = w.at[layer].set(jnp.take(w[layer], perm, axis=0))
    return out


def moe_layer(
    x,
    p,
    expert_to_slot,
    config: ModelConfig,
    policy: ShardingPolicy,
    *,
    capacity_factor: float | None = None,
    seq_sharded_out: bool = False,
    backend: str | None = None,
    shed_enable=None,
    layer=None,
):
    """x (B, S, D) replicated over model → (y (B,S,D), :class:`MoEAux`).

    aux: ``expert_counts`` (E,) tokens routed per *real* expert this call
    (GEM Step-1 hook), ``aux_loss`` load-balance loss (train), ``dropped``
    fraction of assignments dropped at capacity (=
    ``dropped_tokens / (Gd·Ng·k·expert_tp)`` — see
    :class:`~repro.models.dispatch.DispatchPlan`), plus the shed table
    (``overflow_tokens`` / ``shed_tokens`` / ``shed_delta``).

    ``shed_enable`` (traced 0/1 scalar, or None) turns on the
    capacity-overflow shed pass in :func:`build_dispatch` — only
    meaningful with a replica-split table; ``None`` keeps the traced
    program identical to the pre-shed layer.

    ``backend`` overrides ``config.moe_backend`` for this call (see the
    module docstring for the three backends). The body is a pure
    composition of the :mod:`repro.models.dispatch` stages.

    ``expert_to_slot`` is either the (E_v,) router remap table or, under
    the replication plane, an (E_v, P) replica-split table paired with a
    weight pool ``p`` whose expert rows carry the replica copies — the
    physical slot count is read off the stacked weights, so the same layer
    code serves single-copy and replicated pools.

    ``layer`` (int32 scalar, or None): ``p``'s expert weights are then the
    whole (L, …) stack and this is layer ``layer``; the Pallas backend
    reads its weights in place, so a layer scan need not slice them out.
    """
    backend = resolve_moe_backend(backend, config, policy)
    num_slots = int(p["w_gate"].shape[-3])
    if layer is not None and backend != "pallas":
        # only the kernel reads a layer of the stack in place
        p = {**p, **{n: p[n][layer] for n in ("w_gate", "w_up", "w_down")}}
        layer = None
    B, S, D = x.shape
    # `is None`, not falsy-or: an explicit 0.0 means "minimum capacity"
    cf = (
        capacity_factor if capacity_factor is not None
        else config.capacity_factor
    )
    # Dispatch is *grouped by data shard*: tokens of one data-parallel group
    # dispatch among themselves, so the (Gd, E_v, C, D) expert buffers shard
    # over data AND model. A global (E_v, C_global, D) formulation has no
    # data dimension — its buffers replicate across the data axis and every
    # op on them turns into multi-GB cross-data all-reduces (measured on
    # granite train_4k: 16 GB/layer).
    Gd = policy.data_axis_size
    if B % Gd:
        _warn_once(
            ("gd_collapse", B, Gd),
            f"moe_layer: batch B={B} (x shape {tuple(x.shape)}) does not "
            f"divide the data-axis size Gd={Gd}; collapsing to Gd=1 — "
            "data-parallel dispatch grouping is lost and the expert buffers "
            "replicate across the data axis",
        )
        Gd = 1
    N = B * S
    xg = x.reshape(Gd, N // Gd, D)
    xg = policy.constrain(xg, policy.batch, None, None)

    router = route(xg, p["router"], config, policy, backend=backend)

    if backend == "dense_ref":
        # capacity-free oracle: skip dispatch entirely, keep the aux stats
        y = dense_mix(xg, p, router, expert_to_slot, config).reshape(B, S, D)
        y = policy.act_seq_sharded(y) if seq_sharded_out else policy.act_bsd(y)
        return y, MoEAux(
            expert_counts=router.expert_counts,
            aux_loss=router.aux_loss,
            dropped=jnp.asarray(0.0, jnp.float32),
            dropped_tokens=jnp.asarray(0, jnp.int32),
            overflow_tokens=jnp.asarray(0, jnp.int32),
            shed_tokens=jnp.asarray(0, jnp.int32),
            shed_delta=jnp.zeros((num_slots,), jnp.int32),
        )

    plan = build_dispatch(
        router, expert_to_slot, config, policy, capacity_factor=cf,
        num_slots=num_slots, shed_enable=shed_enable,
    )
    y_e = expert_compute(xg, plan, p, config, policy, backend=backend,
                         layer=layer)
    y = combine(y_e, plan, (B, S, D), policy, seq_sharded_out=seq_sharded_out)
    return y, MoEAux(
        expert_counts=router.expert_counts,
        aux_loss=router.aux_loss,
        dropped=plan.dropped,
        dropped_tokens=plan.dropped_tokens,
        overflow_tokens=plan.overflow_tokens,
        shed_tokens=plan.shed_tokens,
        shed_delta=plan.shed_delta,
    )


def moe_layer_dense_ref(x, p, config: ModelConfig):
    """Oracle: every expert computed densely on every token, then mixed.

    Capacity-free, placement-free. Used by unit tests to validate the
    dispatch path (with generous capacity the two must agree).
    """
    B, S, D = x.shape
    xg = x.reshape(1, B * S, D)
    policy = host_policy()
    router = route(xg, p["router"], config, policy, backend="einsum")
    table = identity_placement(config, 1)[0]
    return dense_mix(xg, p, router, table, config).reshape(B, S, D)
