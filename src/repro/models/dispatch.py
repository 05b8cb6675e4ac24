"""Staged MoE dispatch plane: route → build_dispatch → expert_compute → combine.

GEM's whole lever is *which device* each expert's tokens land on, so the
data plane is factored into four explicit stages that pass small typed
structs — the decomposition that lets the compute stage be swapped
per-device (einsum / per-shard Pallas / dense oracle) without touching the
placement-aware scatter/gather around it:

* :func:`route` → :class:`RouterOutput` — router logits → top-k gates/ids
  plus every router statistic GEM's control plane consumes (Step-1
  ``expert_counts``, the Switch-style load-balance ``aux_loss`` and its
  ``density`` / ``probs_mean`` ingredients). Under ``backend="pallas"`` the
  fused router kernel also emits those statistics (masked partial sums per
  tile), so no second (T, E) softmax pass exists on the fast path.
* :func:`build_dispatch` → :class:`DispatchPlan` — virtual-expert ids →
  physical slots through the placement table, sort-based ranking within each
  slot, capacity drop, and the (Gd, E_v, C) scatter indices/gates. Pure
  integer/index work: always plain GSPMD-partitioned jnp, shared by every
  backend.

  **Replica splitting** (:mod:`repro.replication`): when the placement
  table is 2-D — an (E_v, P) ``replica_table`` instead of the (E_v,)
  single-slot map — the slot lookup goes through an extra deterministic
  split stage. Each assignment is first ranked *within its (group, virtual
  expert)* by the same stable sort used for capacity ranking, and rank
  ``r`` lands on physical slot ``table[e, r % P]``. The table interleaves a
  replicated expert's copies in proportion to their speed-proportional
  token shares (Bresenham apportionment, baked in by the planner), so hot
  experts' tokens fan out across their copies — more to faster devices —
  while gates, capacity semantics, and the combine are untouched: only
  *where* the expert compute lands changes. Copies are just extra slots in
  the (Gd, S, C, D) buffers (``num_slots`` ≥ E_v), so neither the kernels
  nor the scatter/gather grow any replication-specific code; a 1-D table
  takes the original path, bit-for-bit.

  **Capacity-overflow shedding** (HarMoEny-style, ROADMAP direction 1):
  with a replica table and a traced ``shed_enable`` operand, a *second*
  dispatch pass re-scatters capacity-overflow assignments onto the free
  rows of the same expert's other live copies (least-loaded first, stable
  rank order) instead of dropping them — the first mechanism that acts
  *inside* a layer's synchronization barrier rather than between layers.
  See :func:`build_dispatch`.
* :func:`expert_compute` — gather tokens into the (Gd, E_v, C, D) buffers
  and run the expert FFN. ``einsum`` uses grouped einsums; ``pallas`` runs
  ``moe_ffn_pallas`` *per device shard* via ``shard_map`` over the
  (data, model) mesh (``kernels.sharded``), each device computing its local
  (E_v/16, C, D) slice with its local weight shard — no einsum fallback.
* :func:`combine` — gate-weighted scatter-add back to token order, as a
  batched-over-groups scatter so GSPMD shards it instead of replicating.

``dense_mix`` is the capacity-free oracle that replaces the
build_dispatch/expert_compute/combine pipeline for ``backend="dense_ref"``;
it still consumes :class:`RouterOutput`, so all three backends share the
staged structure.

Each stage traces under a ``jax.named_scope`` of its own name, so every
operation it lowers to carries ``route`` / ``build_dispatch`` /
``expert_compute`` / ``combine`` in its HLO ``op_name`` metadata, and a
profiler trace of a compiled program can be split by stage.

The structs are registered pytrees: they cross ``jax.jit`` / ``lax.scan``
boundaries intact, and :class:`MoEAux` is what the layer stack scans and the
serving engine reads for Step-1 traces (it also supports ``aux["..."]``
indexing for the older dict-style call sites).
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..kernels.compat import auto_interpret
from ..kernels.sharded import moe_ffn_sharded, topk_router_sharded
from ..sharding.policy import ShardingPolicy

__all__ = [
    "RouterOutput",
    "DispatchPlan",
    "MoEAux",
    "route",
    "slot_capacity",
    "build_dispatch",
    "expert_compute",
    "combine",
    "dense_mix",
]


def slot_capacity(
    num_tokens: int,
    config,
    *,
    capacity_factor: float,
    num_slots: int,
    replicated: bool,
) -> int:
    """Per-slot row capacity C of the dispatch buffers — the single
    source of truth shared by :func:`build_dispatch` and the host-side
    shed-gate pricing (:func:`repro.replication.score.shed_gate_decisions`
    must predict exactly the clamp the data plane will apply).

    ``num_tokens`` is the per-data-group token count Ng. With a replica
    table whose slot count S exceeds E_v, the expected per-slot load
    shrinks by E_v/S (the split spreads each expert over its copies), so
    C scales by the same static factor. Both are Python ints: C is a
    compile-time constant and never retraces.
    """
    E = config.num_experts
    Ev = E * config.expert_tp
    cf = capacity_factor
    if replicated and num_slots > Ev:
        cf = capacity_factor * Ev / num_slots
    return max(int(np.ceil(num_tokens * config.experts_per_token / E * cf)), 1)

_WARNED: set = set()


def _warn_once(key, msg: str) -> None:
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _register(cls, data_fields, meta_fields=()):
    jax.tree_util.register_dataclass(cls, list(data_fields), list(meta_fields))
    return cls


@dataclasses.dataclass(frozen=True)
class RouterOutput:
    """Stage-1 output: the routing decision plus every router statistic.

    gates/ids are grouped by dispatch group: (Gd, Ng, k). The statistics are
    global (reduced over all groups): ``expert_counts`` (E,) i32 top-k
    selections per *real* expert (GEM's Step-1 trace), ``density`` (E,) f32
    = counts / N, ``probs_mean`` (E,) f32 mean softmax probability, and the
    Switch-style ``aux_loss`` = E · Σ density · probs_mean.
    """

    gates: jax.Array
    ids: jax.Array
    expert_counts: jax.Array
    density: jax.Array
    probs_mean: jax.Array
    aux_loss: jax.Array


_register(
    RouterOutput,
    ("gates", "ids", "expert_counts", "density", "probs_mean", "aux_loss"),
)


@dataclasses.dataclass(frozen=True)
class DispatchPlan:
    """Stage-2 output: where every kept assignment lands.

    ``dispatch_idx`` (Gd, E_v, C) i32 — token index (within its group) held
    by each capacity row; ``Ng`` marks the zero pad token. ``dispatch_gate``
    (Gd, E_v, C) f32 — the gate each row is combined with (0 for pad/
    dropped).

    **Drop accounting — two views of one quantity.** The denominator is the
    total number of *assignments* this call made: ``Gd · Ag`` with
    ``Ag = Ng · k · expert_tp`` (every token contributes ``k`` expert picks,
    each split into ``expert_tp`` virtual-expert slices). ``dropped_tokens``
    () i32 is the absolute count of assignments that found no capacity row;
    ``dropped`` () f32 is exactly ``dropped_tokens / (Gd · Ag)`` — the
    legacy fraction older call sites read. The two are pinned to each other
    by a regression test (``tests/test_shed.py::test_drop_accounting_identities``).

    **Shed table.** ``overflow_tokens`` () i32 counts assignments past the
    capacity clamp *before* the shed pass (== ``dropped_tokens`` when
    shedding is off); ``shed_tokens`` () i32 is how many of those the
    second dispatch pass re-scattered onto free replica rows instead of
    dropping, so ``dropped_tokens = overflow_tokens − shed_tokens`` always.
    ``shed_delta`` (S,) i32 is the signed per-slot row delta (+received,
    −sent, summed over groups); a slot either overflows or has free rows,
    never both, so the signs never mix within one slot.
    """

    dispatch_idx: jax.Array
    dispatch_gate: jax.Array
    dropped: jax.Array
    dropped_tokens: jax.Array
    overflow_tokens: jax.Array
    shed_tokens: jax.Array
    shed_delta: jax.Array

    @property
    def capacity(self) -> int:
        return self.dispatch_idx.shape[-1]

    @property
    def num_slots(self) -> int:
        """Physical slot count S (= E_v single-copy; > E_v with replicas)."""
        return self.dispatch_idx.shape[1]

    @property
    def flat_idx(self) -> jax.Array:
        """(Gd, E_v·C) gather/scatter index view shared by stages 3 and 4."""
        Gd = self.dispatch_idx.shape[0]
        return self.dispatch_idx.reshape(Gd, -1)


_register(
    DispatchPlan,
    (
        "dispatch_idx", "dispatch_gate", "dropped", "dropped_tokens",
        "overflow_tokens", "shed_tokens", "shed_delta",
    ),
)


@dataclasses.dataclass(frozen=True)
class MoEAux:
    """Per-call aux the layer stack scans and the engine's Step-1 reads.

    Supports ``aux["expert_counts"]`` indexing for dict-style call sites.

    ``dropped`` is the *fraction* of assignments dropped at capacity and
    ``dropped_tokens`` the absolute count behind it — always related by
    ``dropped = dropped_tokens / (Gd · Ng · k · expert_tp)`` (see
    :class:`DispatchPlan` for the denominator's derivation).
    ``overflow_tokens`` / ``shed_tokens`` / ``shed_delta`` mirror the
    plan's shed table so the serving engine can price and account the
    capacity-overflow shed pass per layer.
    """

    expert_counts: jax.Array
    aux_loss: jax.Array
    dropped: jax.Array
    dropped_tokens: jax.Array
    overflow_tokens: jax.Array
    shed_tokens: jax.Array
    shed_delta: jax.Array

    def __getitem__(self, key: str):
        return getattr(self, key)


_register(
    MoEAux,
    (
        "expert_counts", "aux_loss", "dropped", "dropped_tokens",
        "overflow_tokens", "shed_tokens", "shed_delta",
    ),
)


@jax.named_scope("route")
def route(
    xg, router_w, config: ModelConfig, policy: ShardingPolicy, *, backend: str
) -> RouterOutput:
    """xg (Gd, Ng, D) grouped tokens → :class:`RouterOutput`.

    ``pallas``: the fused router kernel runs per data shard under shard_map
    (host path: directly) and its masked tile reductions provide the aux
    statistics. Other backends: softmax + ``lax.top_k`` + jnp reductions.
    Both select identically (softmax is monotone, ties break to the lowest
    expert id).
    """
    Gd, Ng, _ = xg.shape
    E = config.num_experts
    k = config.experts_per_token
    N = Gd * Ng
    # f32 logits from f32 operands: a bf16 dot rounded and then upcast is one
    # XLA may compute at f32 or not depending on what it fuses with, so the
    # backends' programs would select on different logits and flip near-ties
    logits = jnp.einsum(
        "gnd,de->gne", xg.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    if backend == "pallas":
        data_spec, _ = policy.moe_shard_spec(Gd, E * config.expert_tp)
        gates, ids, probs_sum, counts = topk_router_sharded(
            logits, k, mesh=policy.mesh, data_spec=data_spec,
            interpret=auto_interpret(),
        )
        probs_mean = probs_sum / N
        density = counts.astype(jnp.float32) / N
        expert_counts = counts
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, ids = jax.lax.top_k(probs, k)  # (Gd, Ng, k)
        gates = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        probs_mean = jnp.mean(probs, axis=(0, 1))
        density = jnp.mean(
            jax.nn.one_hot(ids, E, dtype=jnp.float32).sum(axis=2), axis=(0, 1)
        )
        expert_counts = jax.ops.segment_sum(
            jnp.ones_like(ids.reshape(-1), dtype=jnp.int32),
            ids.reshape(-1),
            num_segments=E,
        )
    aux_loss = E * jnp.sum(density * probs_mean)
    return RouterOutput(
        gates=gates, ids=ids, expert_counts=expert_counts,
        density=density, probs_mean=probs_mean, aux_loss=aux_loss,
    )


def _rank_in_group(slots, num_slots: int):
    """Position of each assignment within its slot group (stable order).

    slots: (A,) int32. Returns positions (A,) such that the i-th (in original
    order) assignment of a slot gets position i.
    """
    A = slots.shape[0]
    order = jnp.argsort(slots, stable=True)  # groups together, stable in index
    sorted_slots = jnp.take(slots, order)
    group_sizes = jax.ops.segment_sum(
        jnp.ones((A,), jnp.int32), slots, num_segments=num_slots
    )
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes)[:-1]]
    )
    pos_sorted = jnp.arange(A, dtype=jnp.int32) - jnp.take(starts, sorted_slots)
    inv = jnp.argsort(order, stable=True)
    return jnp.take(pos_sorted, inv), group_sizes


@jax.named_scope("build_dispatch")
def build_dispatch(
    router: RouterOutput,
    expert_to_slot,
    config: ModelConfig,
    policy: ShardingPolicy,
    *,
    capacity_factor: float,
    num_slots: int | None = None,
    shed_enable=None,
) -> DispatchPlan:
    """Routing decision → scatter plan. Backend-independent index work.

    Virtual assignments map through the placement table to physical slots,
    rank within their (group, slot) via the stable sort, and drop beyond the
    static capacity C = ⌈Ng·k/E · cf⌉ (dropped assignments scatter out of
    bounds, ``mode="drop"``). The drop *fraction* and the absolute count it
    abbreviates are both returned and pinned to each other:
    ``dropped = dropped_tokens / (Gd · Ag)`` with ``Ag = Ng · k ·
    expert_tp`` total assignments per group.

    ``expert_to_slot`` is either the (E_v,) single-slot map or an (E_v, P)
    replica-split table (see the module docstring); ``num_slots`` is the
    physical slot count S of the weight pool (default E_v — required when
    the pool carries replica slots, since table contents are traced values).

    **Capacity-overflow shed pass.** With a replica table and
    ``shed_enable`` given (a traced 0/1 scalar — a *scanned operand* under
    the whole-model decode scan, so flipping it never retraces), a second
    dispatch pass re-scatters assignments that overflowed their slot's
    capacity onto the free capacity rows of the *other live copies of the
    same virtual expert*, instead of dropping them. Deterministic by
    construction: overflow assignments are ranked within their (group,
    virtual expert) by the same stable sort the capacity clamp uses, the
    target copies are ordered least-loaded-first (slot id breaks ties, dead
    duplicate-table columns sort last with zero free rows), and rank ``r``
    waterfalls into the ``r``-th free row of that ordering. Overflow beyond
    the copies' total free capacity still drops. ``shed_enable=0`` yields
    bit-identical outputs to the pass being absent; ``shed_enable=None``
    (the default) omits the pass from the traced program entirely, so
    pre-existing executables are structurally unchanged.

    **Replica-aware capacity.** With replica slots (S > E_v and a 2-D
    table) the expected per-slot load shrinks by E_v/S — the split spreads
    each replicated expert's tokens over its copies — so C scales by the
    same static factor instead of staying single-copy sized, cutting the
    (Gd, S, C, D) buffer growth replica slots add (the capacity factor
    still absorbs routing skew, exactly as before). Budget 0 (S = E_v)
    reduces to the original formula bit-for-bit. Both S and E_v are
    static, so migrations and share retargets never change C — the
    scan-fused decode executable's zero-recompile guarantee depends on
    that.
    """
    Gd, Ng, k = router.ids.shape
    E = config.num_experts
    tp = config.expert_tp
    Ev = E * tp
    S = num_slots if num_slots is not None else Ev
    ids = router.ids
    # virtual assignments → physical slots (ranked per data group)
    vids = ids[..., None] * tp + jnp.arange(tp, dtype=ids.dtype)  # (Gd,Ng,k,tp)
    Ag = Ng * k * tp
    vids_flat = vids.reshape(Gd, Ag)
    table = jnp.asarray(expert_to_slot)
    group_of = jnp.repeat(jnp.arange(Gd, dtype=jnp.int32), Ag)
    if table.ndim == 2:
        # replica split: rank within (group, virtual expert) first, then
        # rank%P picks the copy — deterministic, speed-proportional via the
        # table's share-interleaved columns
        P = table.shape[1]
        vkeyed = (group_of * Ev + vids_flat.reshape(-1)).astype(jnp.int32)
        vpos, _ = _rank_in_group(vkeyed, Gd * Ev)
        slots = table[vids_flat, vpos.reshape(Gd, Ag) % P]  # (Gd, Ag)
    else:
        slots = jnp.take(table, vids_flat)  # (Gd, Ag)
    keyed = (group_of * S + slots.reshape(-1)).astype(jnp.int32)
    pos, slot_sizes = _rank_in_group(keyed, Gd * S)
    pos = pos.reshape(Gd, Ag)
    tok_idx = jnp.tile(
        jnp.repeat(jnp.arange(Ng, dtype=jnp.int32), k * tp), (Gd, 1)
    )
    a_gates = jnp.repeat(router.gates.reshape(Gd, -1), tp, axis=1)

    C = slot_capacity(
        Ng, config, capacity_factor=capacity_factor, num_slots=S,
        replicated=table.ndim == 2,
    )
    keep = pos < C
    slot_safe = jnp.where(keep, slots, S)
    gidx = jnp.broadcast_to(
        jnp.arange(Gd, dtype=jnp.int32)[:, None], slots.shape
    )
    dispatch_idx = jnp.full((Gd, S, C), Ng, dtype=jnp.int32)  # Ng → pad row
    dispatch_idx = dispatch_idx.at[gidx, slot_safe, pos].set(
        tok_idx, mode="drop"
    )
    dispatch_gate = jnp.zeros((Gd, S, C), dtype=jnp.float32)
    dispatch_gate = dispatch_gate.at[gidx, slot_safe, pos].set(
        a_gates, mode="drop"
    )

    kept = jnp.sum(keep).astype(jnp.int32)
    overflow_tokens = jnp.asarray(Gd * Ag, jnp.int32) - kept
    shed_tokens = jnp.asarray(0, jnp.int32)
    shed_delta = jnp.zeros((S,), jnp.int32)
    if table.ndim == 2 and shed_enable is not None:
        # ---- capacity-overflow second pass: shed to free replica rows ----
        shed_on = jnp.asarray(shed_enable).astype(jnp.int32) > 0
        P = table.shape[1]
        sizes = slot_sizes.reshape(Gd, S)
        cnt = jnp.minimum(sizes, C)  # kept rows per (group, slot)
        # a table row may repeat a slot (single-copy experts, Bresenham
        # rounding): only the first occurrence is a live copy, duplicates
        # must not double-count its free rows
        dupe = jnp.tril(table[:, :, None] == table[:, None, :], k=-1).any(-1)
        live = ~dupe  # (E_v, P)
        cload = cnt[:, table]  # (Gd, E_v, P) kept rows on each copy
        free = jnp.where(live[None], C - cload, 0)
        # waterfall order: least-loaded live copy first, slot id breaks
        # ties, dead duplicates last (their free rows are already 0)
        okey = jnp.where(
            live[None], cload * (S + 1) + table[None], (C + 1) * (S + 1)
        )
        order = jnp.argsort(okey, axis=-1, stable=True)
        sorted_slot = jnp.take_along_axis(
            jnp.broadcast_to(table[None], cload.shape), order, axis=-1
        )
        cumfree = jnp.cumsum(
            jnp.take_along_axis(free, order, axis=-1), axis=-1
        )  # (Gd, E_v, P)
        # rank overflow assignments within (group, virtual expert) by the
        # same stable sort the capacity clamp used; kept ones park in a
        # sentinel segment so they never consume a rank
        rkey = jnp.where(
            keep.reshape(-1),
            Gd * Ev,
            group_of * Ev + vids_flat.reshape(-1),
        ).astype(jnp.int32)
        orank, _ = _rank_in_group(rkey, Gd * Ev + 1)
        orank = orank.reshape(Gd, Ag)
        cf_a = cumfree[gidx, vids_flat]  # (Gd, Ag, P)
        copy_idx = jnp.sum(cf_a <= orank[..., None], axis=-1)
        shed_ok = orank < cf_a[..., P - 1]
        t_slot = jnp.take_along_axis(
            sorted_slot[gidx, vids_flat],
            jnp.minimum(copy_idx, P - 1)[..., None],
            axis=-1,
        )[..., 0]
        prev_cum = jnp.where(
            copy_idx > 0,
            jnp.take_along_axis(
                cf_a, jnp.maximum(copy_idx - 1, 0)[..., None], axis=-1
            )[..., 0],
            0,
        )
        # rows cnt..C-1 of the target copy are free; the waterfall offset
        # orank − prev_cum is < that copy's free count, so t_pos < C and
        # kept rows (pos < cnt) are never overwritten
        t_pos = cnt[gidx, t_slot] + (orank - prev_cum)
        shed_mask = jnp.logical_and(~keep, shed_ok) & shed_on
        s_slot = jnp.where(shed_mask, t_slot, S)  # S → out-of-bounds drop
        s_pos = jnp.where(shed_mask, t_pos, 0)
        dispatch_idx = dispatch_idx.at[gidx, s_slot, s_pos].set(
            tok_idx, mode="drop"
        )
        dispatch_gate = dispatch_gate.at[gidx, s_slot, s_pos].set(
            a_gates, mode="drop"
        )
        shed_i32 = shed_mask.astype(jnp.int32).reshape(-1)
        recv = jax.ops.segment_sum(
            shed_i32, s_slot.reshape(-1), num_segments=S + 1
        )[:S]
        sent = jax.ops.segment_sum(
            shed_i32,
            jnp.where(shed_mask, slots, S).reshape(-1),
            num_segments=S + 1,
        )[:S]
        shed_delta = (recv - sent).astype(jnp.int32)
        shed_tokens = jnp.sum(shed_i32)
        kept = kept + shed_tokens

    # expert spec adapts: None (replicate) when E_v doesn't divide the
    # model axis — a hard divisibility error from with_sharding_constraint
    # otherwise
    b = policy.batch
    _, es = policy.moe_shard_spec(Gd, S)
    dispatch_idx = policy.constrain(dispatch_idx, b, es, None)
    dispatch_gate = policy.constrain(dispatch_gate, b, es, None)
    # absolute count of capacity-dropped assignments (telemetry's
    # `dispatch.dropped_tokens`) and the legacy fraction it abbreviates:
    # dropped == dropped_tokens / (Gd·Ag), Ag = Ng·k·expert_tp — pinned by
    # the regression test in tests/test_moe.py
    dropped_tokens = jnp.asarray(Gd * Ag, jnp.int32) - kept
    dropped = 1.0 - kept / (Gd * Ag)
    return DispatchPlan(
        dispatch_idx=dispatch_idx, dispatch_gate=dispatch_gate,
        dropped=dropped, dropped_tokens=dropped_tokens,
        overflow_tokens=overflow_tokens, shed_tokens=shed_tokens,
        shed_delta=shed_delta,
    )


@jax.named_scope("expert_compute")
def expert_compute(
    xg,
    plan: DispatchPlan,
    p,
    config: ModelConfig,
    policy: ShardingPolicy,
    *,
    backend: str,
    layer=None,
):
    """Gather per-plan into (Gd, E_v, C, D) buffers, FFN, apply gates.

    The gather stays outside any shard_map (its indices cross shards); only
    the FFN itself runs per-device under ``backend="pallas"``. Returns the
    gate-weighted (Gd, E_v, C, D) expert outputs for :func:`combine`.

    With ``layer`` (``backend="pallas"`` only) the expert weights of
    ``p`` are the whole layer stack, and the kernel reads that layer's in
    place.
    """
    Gd, Ng, D = xg.shape
    Ev = plan.num_slots  # physical slots: E_v, or more under replication
    b = policy.batch
    data_spec, expert_spec = policy.moe_shard_spec(Gd, Ev)
    x_pad = jnp.concatenate([xg, jnp.zeros((Gd, 1, D), xg.dtype)], axis=1)
    x_e = jnp.take_along_axis(
        x_pad, plan.flat_idx[:, :, None], axis=1
    ).reshape(Gd, Ev, plan.capacity, D)
    x_e = policy.constrain(x_e, b, expert_spec, None, None)
    indivisible = (
        policy.mesh is not None and expert_spec is None
        and policy.model_axis_size > 1
    )
    if backend == "pallas":
        # the padded spec applies only inside the kernel's shard_map; the
        # surrounding constraints stay on the real (indivisible) E_v
        pad_to, kernel_expert_spec = None, expert_spec
        if indivisible:
            Ev_pad, pad_spec = policy.moe_expert_pad(Ev)
            if pad_spec is not None:
                pad_to, kernel_expert_spec = Ev_pad, pad_spec
                _warn_once(
                    ("moe_expert_padded", Ev, policy.model_axis_size),
                    f"moe_layer: E_v={Ev} does not divide the model-axis "
                    f"size {policy.model_axis_size}; padding the expert dim "
                    f"to {Ev_pad} with dead slots so the per-shard kernels "
                    "stay sharded (pad rows compute zeros and are sliced "
                    "off)",
                )
        y_e = moe_ffn_sharded(
            x_e, p["w_gate"], p["w_up"], p["w_down"],
            mesh=policy.mesh, data_spec=data_spec,
            expert_spec=kernel_expert_spec,
            block_c=config.pallas_block_c, block_f=config.pallas_block_f,
            interpret=auto_interpret(), pad_expert_to=pad_to, layer=layer,
        )
    else:
        w_gate, w_up, w_down = p["w_gate"], p["w_up"], p["w_down"]
        xe = x_e
        pad_spec, Ev_pad = None, Ev
        if indivisible:
            # mirror the pallas dead-slot path: pad the expert dim to the
            # model axis with zero rows so the GSPMD einsums shard instead
            # of replicating (pad rows compute zeros and are sliced off)
            Ev_pad, pad_spec = policy.moe_expert_pad(Ev)
            if pad_spec is not None:
                pad = Ev_pad - Ev
                _warn_once(
                    ("moe_expert_padded_einsum", Ev, policy.model_axis_size),
                    f"moe_layer: E_v={Ev} does not divide the model-axis "
                    f"size {policy.model_axis_size}; padding the expert dim "
                    f"to {Ev_pad} with dead slots so the GSPMD einsums stay "
                    "sharded (pad rows compute zeros and are sliced off)",
                )
                xe = jnp.pad(x_e, ((0, 0), (0, pad), (0, 0), (0, 0)))
                xe = policy.constrain(xe, b, pad_spec, None, None)
                w_gate = jnp.pad(w_gate, ((0, pad), (0, 0), (0, 0)))
                w_up = jnp.pad(w_up, ((0, pad), (0, 0), (0, 0)))
                w_down = jnp.pad(w_down, ((0, pad), (0, 0), (0, 0)))
        # fp32 accumulation and hidden activations, rounded once before the
        # down projection: the kernel's (and kernels/ref.py's) math, so the
        # two backends agree in bf16 instead of drifting apart over layers.
        # The TPU compiler folds the operand upcasts into the bf16 MXU dot
        # (the same program as preferred_element_type=f32, which XLA:CPU
        # cannot run inside a scan)
        f32 = jnp.float32
        xf = xe.astype(f32)
        h_gate = jnp.einsum("gecd,edf->gecf", xf, w_gate.astype(f32))
        h_up = jnp.einsum("gecd,edf->gecf", xf, w_up.astype(f32))
        h = (jax.nn.silu(h_gate) * h_up).astype(xe.dtype)
        h = policy.constrain(
            h, b, pad_spec if pad_spec is not None else expert_spec, None, None
        )
        y_e = jnp.einsum(
            "gecf,efd->gecd", h.astype(f32), w_down.astype(f32)
        ).astype(xe.dtype)
        if pad_spec is not None:
            y_e = y_e[:, :Ev]
    y_e = y_e * plan.dispatch_gate[..., None].astype(y_e.dtype)
    return policy.constrain(y_e, b, expert_spec, None, None)


@jax.named_scope("combine")
def combine(
    y_e,
    plan: DispatchPlan,
    out_shape: tuple,
    policy: ShardingPolicy,
    *,
    seq_sharded_out: bool = False,
):
    """(Gd, E_v, C, D) expert outputs → (B, S, D) token-ordered residual.

    Batched scatter-add per group: the group dim must be a *batching*
    dimension (vmap), not an explicit index array — GSPMD shards batched
    scatters over the batch axis but falls back to replicate + global
    all-reduce for the index-array form (measured: 2×6.4 GB/layer ARs).
    """
    B, S, D = out_shape
    Gd = y_e.shape[0]
    Ng = (B * S) // Gd
    b, m = policy.batch, policy.model_axis
    y = jax.vmap(
        lambda idx_g, upd_g: jnp.zeros((Ng + 1, D), y_e.dtype)
        .at[idx_g]
        .add(upd_g, mode="drop")
    )(plan.flat_idx, y_e.reshape(Gd, -1, D))
    y = policy.constrain(y, b, m if seq_sharded_out else None, None)
    y = y[:, :Ng].reshape(B, S, D)
    if seq_sharded_out:
        # land sequence-sharded: the combine's cross-model sum becomes a
        # reduce-scatter instead of all-reduce-then-slice
        return policy.act_seq_sharded(y)
    return policy.act_bsd(y)


def dense_mix(xg, p, router: RouterOutput, expert_to_slot,
              config: ModelConfig):
    """Capacity-free oracle replacing stages 2–4 for ``dense_ref``.

    Every expert computed on every token, mixed by the routing decision.
    The stacked weights live in *slot* order (physical placement); gather
    them back to virtual-expert order so the oracle stays
    placement-invariant like the dispatch path. Under replication (2-D
    table) any copy serves — copies are bit-identical rows, so the first
    column suffices. Returns (Gd, Ng, D).
    """
    Gd, Ng, D = xg.shape
    E, tp = config.num_experts, config.expert_tp
    k = config.experts_per_token
    table = jnp.asarray(expert_to_slot)
    if table.ndim == 2:
        table = table[:, 0]
    pv = dict(p)
    for name in ("w_gate", "w_up", "w_down"):
        pv[name] = jnp.take(p[name], table, axis=0)
    xf = xg.reshape(Gd * Ng, D)
    gates = router.gates.reshape(Gd * Ng, k)
    ids = router.ids.reshape(Gd * Ng, k)
    h_gate = jnp.einsum("nd,edf->nef", xf, pv["w_gate"])
    h_up = jnp.einsum("nd,edf->nef", xf, pv["w_up"])
    h = jax.nn.silu(h_gate) * h_up
    y_all = jnp.einsum("nef,efd->ned", h, pv["w_down"])  # (N, E_v, D)
    y_real = y_all.reshape(xf.shape[0], E, tp, -1).sum(axis=2)  # (N, E, D)
    sel = jax.nn.one_hot(ids, E, dtype=y_real.dtype) * gates[..., None].astype(
        y_real.dtype
    )
    return jnp.einsum("nke,ned->nd", sel, y_real).reshape(Gd, Ng, D)
