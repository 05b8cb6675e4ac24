"""Collective expert-row migration: ppermute weight moves under shard_map.

The migration plane's batches used to reach the stacked expert weights as a
host-side row gather — correct, but never the device traffic the
:class:`~repro.core.latency_model.MigrationCostModel` prices. This module
executes a batch as the *actual* collectives on the expert-sharded weights,
inside the same ``(data, model)`` mesh the dispatch plane's kernels run
under:

* :func:`swap_expert_rows` — a two-slot swap batch as pairwise ``ppermute``
  rounds over the model axis (each swap: the two shards exchange one expert
  row each in a single round).
* :func:`broadcast_expert_row` — a replica add/drop as a one-to-many
  broadcast (one round per destination shard; the source re-reads its
  pre-batch row each round).
* :func:`apply_row_sources` — the general entry point both reduce to: any
  per-layer ``(S,)`` row-source map, lowered by
  :func:`~repro.online.migration.lower_row_sources` into a
  :class:`~repro.online.migration.CollectiveSchedule` and executed as a
  local pre-batch gather plus the schedule's ppermute rounds.

Every read — the local gather and every round's send — addresses the
**pre-batch** block, so the affected rows are naturally double-buffered:
read-before-overwrite ordering cannot be violated no matter how rounds are
packed, which is exactly what lets the copy overlap decode compute on
hardware (the overlap factor ``MigrationConfig.overlap_fraction`` models).

The returned :class:`CollectiveStats` report what the schedule *actually*
shipped (cross-shard rows, payload bytes, rounds) — measured traffic the
serving engine records against the cost model's charge and feeds the
:class:`~repro.core.latency_model.BandwidthEstimator`.

Specs come from :meth:`ShardingPolicy.expert_collective_axis`; with
``mesh=None`` there is no interconnect and callers take the host gather
path instead (see :func:`repro.models.moe.apply_layer_permutation`).

**Schedule-generic executable.** :func:`apply_row_sources` bakes its
lowered schedule into the traced program, so every applied batch pays a
fresh jit (~0.3 s) — fine at load time, fatal at decode cadence.
:class:`MigrationExecutable` is the serving-loop form: one jit traced
*once* whose (L, S) row-source map is a **traced operand**, read by a
loop over layers that rewrites the donated stacks one layer at a time.
``ppermute``'s permutation must be static, so the operand-driven exchange uses
``lax.all_to_all`` instead — every shard offers each peer the local rows
that peer's slots want (readable off the traced map), and each receiver
selects by owner shard; a dense exchange whose *program* is
batch-independent, which is exactly what makes applying any migration —
including mid-run ones — compile-free and allocation-free (weight buffers
are donated, so the swap is in-place at the XLA level). Identity rows pass
through untouched, so one dense (L, S) operand covers the whole stack
(:func:`repro.online.migration.dense_step_sources`). Traffic accounting
still comes from the host-side schedule lowering
(:func:`stats_for_dense_sources`) — the measured-vs-modeled contract is
about the *minimal* schedule a hardware transport would ship.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..online.migration import (
    CollectiveSchedule,
    RowTransfer,
    lower_row_sources,
)

__all__ = [
    "CollectiveStats",
    "MigrationExecutable",
    "apply_row_sources",
    "stats_for_dense_sources",
    "swap_expert_rows",
    "broadcast_expert_row",
]


@dataclasses.dataclass(frozen=True)
class CollectiveStats:
    """What one executed schedule actually moved (measured, not modeled)."""

    rows_rewritten: int  # slots whose weight row changed
    cross_rows: int  # rows shipped over the interconnect (ppermute payload)
    local_rows: int  # rows copied within their own shard's HBM
    rounds: int  # ppermute rounds (collective launches)
    payload_bytes: int  # interconnect bytes across all weight arrays

    def __add__(self, other: "CollectiveStats") -> "CollectiveStats":
        return CollectiveStats(
            self.rows_rewritten + other.rows_rewritten,
            self.cross_rows + other.cross_rows,
            self.local_rows + other.local_rows,
            self.rounds + other.rounds,
            self.payload_bytes + other.payload_bytes,
        )

    @staticmethod
    def zero() -> "CollectiveStats":
        return CollectiveStats(0, 0, 0, 0, 0)


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _round_tables(rnd: list[RowTransfer], num_shards: int):
    """Static per-shard send/receive tables of one ppermute round."""
    send_idx = np.zeros(num_shards, dtype=np.int32)
    recv_idx = np.zeros(num_shards, dtype=np.int32)
    is_dst = np.zeros(num_shards, dtype=bool)
    perm = []
    for t in rnd:
        send_idx[t.src_shard] = t.src_idx
        recv_idx[t.dst_shard] = t.dst_idx
        is_dst[t.dst_shard] = True
        perm.append((t.src_shard, t.dst_shard))
    return send_idx, recv_idx, is_dst, perm


def _stats_for(schedule: CollectiveSchedule, arrays) -> CollectiveStats:
    row_bytes = sum(
        int(np.prod(a.shape[1:])) * a.dtype.itemsize for a in arrays
    )
    return CollectiveStats(
        rows_rewritten=schedule.cross_rows + schedule.local_rows,
        cross_rows=schedule.cross_rows,
        local_rows=schedule.local_rows,
        rounds=schedule.num_rounds,
        payload_bytes=schedule.cross_rows * row_bytes,
    )


def apply_row_sources(
    arrays,
    src,
    *,
    mesh,
    axis: str = "model",
    schedule: CollectiveSchedule | None = None,
):
    """Apply ``new_rows = old_rows[src]`` to expert-sharded weight arrays
    with collectives, returning ``(new_arrays, CollectiveStats)``.

    ``arrays`` is a tuple of ``(S, …)`` arrays whose leading slot dim is
    sharded over mesh axis ``axis`` (any other mesh axes see the weights
    replicated, as the dispatch plane's ``w_expert`` specs lay them out);
    one slot's rows across all arrays travel together, so a round's payload
    is exactly one expert's stacked weights. ``src`` is the batch's static
    (S,) row-source map; pass ``schedule`` to reuse an existing lowering.

    Execution: (1) every shard gathers its same-shard sources from its
    pre-batch block; (2) each round, source shards read their pre-batch row
    (double buffer), one ``ppermute`` moves the payloads, and destination
    shards write them at their static local indices. The per-round tables
    are static host data, so the only device traffic is the row payloads —
    which is what :class:`CollectiveStats` reports.
    """
    arrays = tuple(arrays)
    if schedule is None:
        schedule = lower_row_sources(src, mesh.shape[axis])
    n = schedule.num_shards
    if n != mesh.shape[axis]:
        raise ValueError(
            f"schedule lowered for {n} shards but mesh axis "
            f"{axis!r} has {mesh.shape[axis]}"
        )
    stats = _stats_for(schedule, arrays)
    if stats.rows_rewritten == 0:
        return arrays, stats

    lsrc = jnp.asarray(schedule.local_src)
    rounds = [_round_tables(rnd, n) for rnd in schedule.rounds]

    def per_shard(*blks):
        shard = jax.lax.axis_index(axis)
        my_src = lsrc[shard]
        new = [blk[my_src] for blk in blks]
        for send_idx, recv_idx, is_dst, perm in rounds:
            si = jnp.asarray(send_idx)[shard]
            ri = jnp.asarray(recv_idx)[shard]
            receiver = jnp.asarray(is_dst)[shard]
            # send side reads the PRE-batch block — the double buffer
            payload = tuple(
                jax.lax.dynamic_index_in_dim(blk, si, 0, keepdims=False)
                for blk in blks
            )
            got = tuple(
                jax.lax.ppermute(p, axis, perm) for p in payload
            )
            new = [
                nb.at[ri].set(jnp.where(receiver, g, nb[ri]))
                for nb, g in zip(new, got)
            ]
        return tuple(new)

    specs = tuple(P(*((axis,) + (None,) * (a.ndim - 1))) for a in arrays)
    # jit the whole schedule into one executable: eager shard_map dispatches
    # every round's ops device-by-device (~50× slower on the forced host
    # platform); the schedule is static per call, so this is one compile
    mapped = jax.jit(
        _shard_map(per_shard, mesh, in_specs=specs, out_specs=specs)
    )
    return mapped(*arrays), stats


def swap_expert_rows(arrays, swaps, *, mesh, axis: str = "model"):
    """Exchange expert rows pairwise: ``swaps`` is a sequence of global
    ``(slot_a, slot_b)`` pairs applied in order (a migration batch's swap
    list). Cross-shard pairs lower to pairwise ppermute rounds; same-shard
    pairs to local row copies. Returns ``(new_arrays, CollectiveStats)``."""
    S = int(arrays[0].shape[0])
    src = np.arange(S, dtype=np.int32)
    for a, b in swaps:
        src[[a, b]] = src[[b, a]]
    return apply_row_sources(arrays, src, mesh=mesh, axis=axis)


def stats_for_dense_sources(src, num_shards: int, row_bytes: int):
    """Per-layer measured traffic for a dense (L, S) row-source operand.

    The executable ships a dense ``all_to_all`` whose wire traffic XLA
    owns; the *accountable* traffic — what a row-level transport would
    ship, and what the cost model prices — is the minimal schedule each
    layer's map lowers to. Returns ``[(layer, CollectiveStats), …]`` for
    layers whose map is not the identity (``row_bytes`` = one slot's
    bytes summed over the weight arrays).
    """
    src = np.asarray(src)
    out = []
    for layer in range(src.shape[0]):
        row = src[layer]
        if np.array_equal(row, np.arange(row.shape[0])):
            continue
        sched = lower_row_sources(row, num_shards)
        out.append((layer, CollectiveStats(
            rows_rewritten=sched.cross_rows + sched.local_rows,
            cross_rows=sched.cross_rows,
            local_rows=sched.local_rows,
            rounds=sched.num_rounds,
            payload_bytes=sched.cross_rows * row_bytes,
        )))
    return out


def _swap_tables(tables, src):
    """Device-side router-table update for a permutation source map.

    ``new_e2s[l, e] = inv_src[l, e2s[l, e]]`` where ``inv_src`` is the
    per-layer inverse permutation (``inv_src[l, src[l, s]] = s``): the
    expert that lived at slot ``s`` now lives at the slot that *sourced
    from* ``s``. Only valid when every layer's map is a permutation —
    migration swap batches always are; replica add/drops are not and
    keep the host-side table recompute.
    """
    L, S = src.shape
    inv = jnp.zeros((L, S), jnp.int32).at[
        jnp.arange(L)[:, None], src
    ].set(jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (L, S)))
    return jnp.take_along_axis(inv, tables.astype(jnp.int32), axis=1)


class MigrationExecutable:
    """One jitted, schedule-generic migration apply for the serving loop.

    ``__call__(src, tables, w_gate, w_up, w_down)`` rewrites the stacked
    ``(L, S, …)`` expert pool to ``new[l] = old[l][src[l]]`` and, when
    ``tables`` (the (L, E_v) expert→slot map) is given, swaps it on
    device in the same dispatch — the router-table update rides the same
    executable as the weight exchange. Returns
    ``((w_gate, w_up, w_down), new_tables_or_None)``.

    The row-source map is a traced operand, so the jit is traced once
    per signature (tables present/absent) and **every subsequent
    migration batch — any swap set, any layer subset, mid-run — reuses
    the compiled executable**: zero traces on apply, which the engine's
    trace counters assert. The stacks are rewritten one layer at a time
    inside that one executable: a layer's rows are gathered, then written
    over the layer in place, so an apply holds one layer of one weight
    beside the stacks and never a second stack (the ``migrate`` device
    scope; the ``migrate.expert_bytes`` counter adds the bytes of the
    rows that took another slot's weights). With ``mesh`` the exchange runs as a
    ``lax.all_to_all`` under ``shard_map`` over mesh axis ``axis``; with
    ``mesh=None`` it is the jitted host gather. Weight buffers are
    donated (in-place rewrite) except on the CPU backend, where XLA
    does not implement donation and would warn per call; callers that
    reuse their input arrays pass ``donate=False``.
    """

    def __init__(self, *, mesh=None, axis: str = "model",
                 donate: bool = True, telemetry=None):
        self.mesh = mesh
        self.axis = axis
        self.trace_count = 0  # bumped by the traced closure: 1 per trace
        # optional repro.telemetry.Telemetry hub: mirrors each trace onto
        # the ``jit.trace.migrate`` counter (the registry is the engine's
        # single source of truth for trace counts)
        self.telemetry = telemetry

        if mesh is None:
            fn = self._host_apply
        else:
            n = int(mesh.shape[axis])

            def exchange(src, *blks):
                # blks: this shard's (L, per, …) blocks; src replicated
                me = jax.lax.axis_index(axis)
                per = blks[0].shape[1]

                def new_layer(l, b):
                    wants = src[l].reshape(n, per)  # rows each shard needs
                    owner = wants // per
                    loc = wants % per
                    own_me = jax.lax.dynamic_index_in_dim(
                        owner, me, 0, keepdims=False)
                    # offer every peer the local rows its slots want
                    # (identity rows ride along; XLA owns the wire), then
                    # keep what this shard's true owners sent
                    outgoing = b[l][loc]  # (n, per, …)
                    recv = jax.lax.all_to_all(outgoing, axis, 0, 0)
                    return recv[own_me, jnp.arange(per)]

                return _rewrite_by_layer(blks, new_layer)

            def fn(src, tables, *ws):
                self._count_trace()
                wspecs = tuple(
                    P(*((None, axis) + (None,) * (w.ndim - 2)))
                    for w in ws
                )
                mapped = _shard_map(
                    exchange, mesh,
                    in_specs=(P(None, None),) + wspecs,
                    out_specs=wspecs,
                )
                with jax.named_scope("migrate"):
                    new_ws = mapped(src, *ws)
                new_tables = (None if tables is None
                              else _swap_tables(tables, src))
                return new_ws, new_tables

        donate_ws = donate and jax.default_backend() != "cpu"
        self._apply = jax.jit(
            fn, donate_argnums=(2, 3, 4) if donate_ws else ())

    def _count_trace(self) -> None:
        self.trace_count += 1
        if self.telemetry is not None:
            self.telemetry.counter("jit.trace.migrate").inc()

    def _host_apply(self, src, tables, *ws):
        self._count_trace()

        def new_layer(l, w):
            # row s of the layer ← row src[l, s] of the same layer, copied
            # row by row into one layer's buffer. A one-op gather of the
            # layer's rows is no faster, and for v5e XLA gives it ≈ 2.4
            # layers of temporaries at mixtral's widths and a copy of the
            # whole stack at granite's
            def row(s, buf):
                start = (l, src[l, s]) + (0,) * (w.ndim - 2)
                r = jax.lax.dynamic_slice(w, start, (1, 1) + w.shape[2:])
                return jax.lax.dynamic_update_slice_in_dim(buf, r[0], s, 0)

            return jax.lax.fori_loop(0, w.shape[1], row,
                                     jnp.zeros(w.shape[1:], w.dtype))

        with jax.named_scope("migrate"):
            new_ws = _rewrite_by_layer(ws, new_layer)
        new_tables = None if tables is None else _swap_tables(tables, src)
        return new_ws, new_tables

    def __call__(self, src, tables, w_gate, w_up, w_down):
        if self.telemetry is not None:
            # the rows whose source is another slot, in all three stacks
            host = np.asarray(src)
            moved = np.count_nonzero(host != np.arange(host.shape[1]))
            self.telemetry.counter("migrate.expert_bytes").inc(
                moved * sum(w.nbytes for w in (w_gate, w_up, w_down))
                // host.size)
        src = jnp.asarray(src, jnp.int32)
        return self._apply(src, tables, w_gate, w_up, w_down)


def _rewrite_by_layer(ws, new_layer):
    """Rewrite the stacked ``(L, S, …)`` arrays ``ws`` one layer at a
    time: layer ``l`` of each array ``w`` becomes ``new_layer(l, w)``,
    which reads only layer ``l`` of ``w``, not yet rewritten. One loop
    per array carries its (donated) stack and updates each layer in
    place, and the loops run one after another, so the only temporary
    is one layer of one array, never a second stack."""
    def rewrite(w):
        def body(l, w):
            return jax.lax.dynamic_update_index_in_dim(
                w, new_layer(l, w), l, 0)

        return jax.lax.fori_loop(0, w.shape[0], body, w)

    return tuple(rewrite(w) for w in ws)


def broadcast_expert_row(arrays, src_slot: int, dst_slots, *, mesh,
                         axis: str = "model"):
    """Overwrite every slot in ``dst_slots`` with the row at ``src_slot`` —
    the replica add/drop primitive (one row rewrite per destination, half a
    swap's traffic). Destinations on the source's own shard are local HBM
    copies; each remote destination shard costs one ppermute round's
    payload. Returns ``(new_arrays, CollectiveStats)``."""
    S = int(arrays[0].shape[0])
    src = np.arange(S, dtype=np.int32)
    for d in dst_slots:
        src[int(d)] = int(src_slot)
    return apply_row_sources(arrays, src, mesh=mesh, axis=axis)
