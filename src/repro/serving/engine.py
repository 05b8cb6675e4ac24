"""Continuous-batching serving engine with GEM integrated end-to-end.

The engine runs the real JAX data plane (prefill + batched decode over a
fixed slot pool) and the full GEM control plane:

  * **Step-1** — every decode step's router output (per-layer per-expert
    token counts, surfaced by the MoE layer as aux) feeds the
    :class:`~repro.core.gem.GEMPlanner` trace collectors.
  * **Step-2** — a fleet variability profile is attached at construction
    (measured on hardware; simulated staircase curves on this container,
    mirroring the paper's power-cap emulation).
  * **Step-3/4** — after ``trace_length`` warm-up steps the planner searches
    a placement; the engine then *re-permutes the stacked expert weights*
    (`apply_placement`) and swaps the router remap tables — the same
    in-deployment expert swap vLLM's EPLB performs.

**Online mode** (``EngineConfig.online=True``) replaces the one-shot
step-counter replan with the :mod:`repro.online` adaptation plane: an
:class:`~repro.online.controller.OnlineController` watches the same Step-1
counts for task-mix drift and the per-device latencies for variability
drift, replans when either fires, and hands back budgeted migration
batches. Each batch flattens to one dense (L, S) row-source operand
(:func:`~repro.online.migration.dense_step_sources`) applied through the
schedule-generic
:class:`~repro.kernels.collective.MigrationExecutable` between decode
steps — one jit traced at engine construction, zero new traces per batch,
with the router tables swapped on device in the same dispatch so weights
and routing never disagree — and charges the batch's migration cost to
that step's simulated latency. ``set_true_profile`` lets a harness inject a mid-run
fleet change (e.g. a power cap) the believed profile doesn't know about;
the controller's variability detector then repairs the belief from the
observed/predicted ratio, exactly as wall-clock timers would on hardware.

Because wall-clock on this CPU container is meaningless for TPU latency
claims, the engine also replays every step's observed expert counts through
the fleet latency model, accumulating the *simulated* step latency that the
paper's figures of merit (e2e latency, TPOT percentiles) are computed from.
On real hardware the same counters would be wall-clock timestamps.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections import deque
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.gem import GEMPlanner
from ..core.score import step_cost_matrix, step_token_matrix
from ..core.types import GEMConfig, Placement, VariabilityProfile
from ..models.model import (
    decode_step,
    init_decode_cache,
    init_paged_decode_cache,
    prefill,
)
from ..models.dispatch import slot_capacity
from ..models.moe import (
    apply_placement,
    identity_placement,
)
from ..online import (
    DriftConfig,
    MigrationConfig,
    OnlineConfig,
    OnlineController,
)
from ..kernels.collective import (
    MigrationExecutable,
    stats_for_dense_sources,
)
from ..online.migration import (
    replica_install_phases,
    replica_source_permutation,
)
from ..replication import (
    ReplicatedPlacement,
    ReplicationConfig,
    plan_replicated_layers,
    replica_fetch_rows,
    replicated_step_cost_matrix,
    replicated_step_token_matrix,
    shed_adjusted_step_cost_matrix,
    shed_device_deltas,
    shed_gate_decisions,
)
from ..sharding.policy import ShardingPolicy
from ..telemetry import (
    AttributionAccumulator,
    RegretTracker,
    Telemetry,
    attribute_step,
)
from ..telemetry.regret import record_step_metrics
from .arrivals import RequestSpec
from .kv_cache import (
    PagedKVConfig,
    PagedKVPool,
    blocks_for_tokens,
    kv_pool_bytes,
    replica_slots_for_headroom,
)
from .sampling import sample
from .scheduler import Request, Scheduler
from .shed import ShedConfig, default_token_bytes
from .slo import slo_report

__all__ = ["EngineConfig", "ServingEngine"]

# fixed histogram buckets for per-step straggler slack (seconds) —
# deterministic boundaries so CI can pin exported snapshots (per-step
# regret rides the same decade ladder — telemetry/regret.py)
_ATTR_SLACK_BOUNDS = (1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 8
    max_len: int = 512
    temperature: float = 0.0
    gem: GEMConfig = GEMConfig()
    placement_policy: str = "gem"  # gem | eplb | linear
    replan_after: int | None = None  # engine steps before replan (default:
    # gem.trace_length; 0 means "as soon as the trace collectors fill")
    other_time_per_step: float = 0.0  # simulated non-MoE per-step latency
    moe_backend: str | None = None  # override ModelConfig.moe_backend for
    # the engine's data plane (einsum | pallas | dense_ref)
    # --- whole-model decode executable (models/model.py) ---
    # "scan" compiles the decode step as ONE lax.scan executable whose
    # per-layer router/replica tables and slot layouts are scanned
    # operands — any placement or mid-run migration reuses the compiled
    # program (jit_trace_counts stays flat). "python" unrolls the same
    # body per layer: the parity baseline.
    decode_mode: str = "scan"
    # --- expert replication plane (repro.replication) ---
    # replica_slots>0 installs a replicated weight pool (E_v + G·slots rows
    # per layer) and replica-split router tables; plans come from the
    # replication-aware planner and step costs use the speed-proportional
    # split. Requires the gem policy and an attached profile.
    replication: ReplicationConfig = ReplicationConfig()
    # --- capacity-overflow token shedding (serving/shed.py) ---
    # enabled=True arms the dispatch plane's second scatter pass: each
    # step the engine prices the shed-vs-wait gate per layer
    # (core/score.shed_decisions, one step behind) and feeds the (L,)
    # enable flags into the decode executable as a scanned operand —
    # flipping them never retraces. Needs a replicated pool
    # (replication.replica_slots > 0): overflow can only re-seat on a
    # live replica row.
    shed: ShedConfig = ShedConfig()
    # --- online adaptation plane (repro.online) ---
    online: bool = False  # drift-triggered replans + budgeted partial swaps
    # instead of the one-shot step-counter replan above
    drift: DriftConfig = DriftConfig()
    migration: MigrationConfig = MigrationConfig()
    replan_cooldown: int = 32  # min steps between drift replans
    payback_horizon: int = 1024  # steps a migration's gain must amortise over
    staggered_replan: bool = False  # load-drift replans re-search only the
    # layers the detector localises the shift to (OnlineConfig.staggered_replan)
    # --- migration data plane (repro.kernels.collective) ---
    # "host": batches apply as host-side row gathers (load-time semantics).
    # "collective": batches lower to ppermute rounds on the expert-sharded
    # weights under the policy's mesh; each applied batch's measured
    # interconnect traffic is recorded against the cost model's charge
    # (engine.migration_records) and fed to the controller's bandwidth
    # estimator. Falls back to the host gather — bit-identical — when the
    # policy has no live expert sharding.
    migration_via: str = "host"
    # --- continuous-batching serving plane (repro.serving) ---
    # kv_mode "auto" pages the KV cache (serving/kv_cache.py) on
    # attention-family archs without a sliding window when the policy has
    # no mesh (the paged pool is unsharded); "paged"/"dense" force. The
    # dense path is the pre-paging layout, kept bit-identical.
    kv_mode: str = "auto"  # auto | paged | dense
    kv: PagedKVConfig = PagedKVConfig()
    # chunked prefill: >0 spreads a prompt's *simulated* prefill time over
    # ceil(P/chunk) engine steps (admission pacing + TTFT accounting); the
    # prefill kernel itself still runs once, when the last chunk lands
    prefill_chunk: int = 0
    prefill_time_per_token: float = 0.0  # simulated prefill s/token
    admit_lookahead: int = 8  # scheduler head-of-line lookahead window
    # optional TTFT service target (sim-seconds). When set, admission
    # records each request's remaining slack (target minus queue age) in
    # the sched.ttft_slack_s histogram and counts already-late admissions
    # in sched.slo_at_risk. None leaves only the queue-age histogram.
    ttft_slo_s: float | None = None
    # per-device HBM budget shared by the paged KV pool and the expert
    # replica pool; required when replication.auto_slots derives
    # replica_slots from what the KV pool leaves free
    hbm_budget_bytes: float | None = None


def _donate_caches() -> bool:
    """Whether the paged decode and install donate the KV pools they are
    given: on accelerators. On the CPU backend callers may keep a step's
    input cache after the step, which a donated buffer would not survive."""
    return jax.default_backend() != "cpu"


class ServingEngine:
    def __init__(
        self,
        params,
        config: ModelConfig,
        policy: ShardingPolicy,
        engine_config: EngineConfig = EngineConfig(),
        *,
        profile: VariabilityProfile | None = None,
        num_devices: int | None = None,
        telemetry: Telemetry | None = None,
    ):
        if engine_config.moe_backend is not None:
            config = dataclasses.replace(
                config, moe_backend=engine_config.moe_backend
            )
        if engine_config.migration_via not in ("host", "collective"):
            raise ValueError(
                f"migration_via={engine_config.migration_via!r} not in "
                "('host', 'collective')"
            )
        if engine_config.decode_mode not in ("scan", "python"):
            raise ValueError(
                f"decode_mode={engine_config.decode_mode!r} not in "
                "('scan', 'python')"
            )
        # --- paged-KV resolution (continuous-batching serving plane) ---
        family_ok = (
            not (config.is_ssm or config.is_hybrid)
            and config.sliding_window == 0
        )
        if engine_config.kv_mode == "auto":
            # the paged pool is unsharded, so a live mesh keeps the proven
            # dense layout; host-scale serving gets paging by default
            self.paged = family_ok and policy.mesh is None
        elif engine_config.kv_mode == "paged":
            if not family_ok:
                raise ValueError(
                    "kv_mode='paged' needs an attention-family arch without "
                    "a sliding window (SSM state is O(1) per slot; SWA ring "
                    "ages don't survive the block indirection)"
                )
            self.paged = True
        elif engine_config.kv_mode == "dense":
            self.paged = False
        else:
            raise ValueError(
                f"kv_mode={engine_config.kv_mode!r} not in "
                "('auto', 'paged', 'dense')"
            )
        block_size = engine_config.kv.block_size
        self._n_max = -(-engine_config.max_len // block_size)
        num_blocks = engine_config.kv.num_blocks
        if num_blocks is None:
            # degenerate sizing: every slot holds a full-length request, so
            # admission never fails and the paged engine behaves densely
            num_blocks = 1 + engine_config.max_batch * self._n_max
        self._kv_num_blocks = num_blocks
        dtype_bytes = jax.tree.leaves(params)[0].dtype.itemsize
        if engine_config.replication.auto_slots:
            # HBM-aware replica budget: replica copies get whatever the KV
            # pool leaves free of the device budget (one budget, not two)
            if engine_config.hbm_budget_bytes is None or not config.is_moe:
                raise ValueError(
                    "replication.auto_slots needs a MoE config and "
                    "EngineConfig.hbm_budget_bytes — the replica budget is "
                    "derived from the paged KV pool's headroom"
                )
            pool_blocks = (
                num_blocks if self.paged
                else 1 + engine_config.max_batch * self._n_max
            )
            pool_bytes = kv_pool_bytes(
                pool_blocks, block_size, config.num_layers,
                config.num_kv_heads, config.head_dim, dtype_bytes,
            )
            engine_config = dataclasses.replace(
                engine_config,
                replication=dataclasses.replace(
                    engine_config.replication,
                    auto_slots=False,
                    replica_slots=replica_slots_for_headroom(
                        engine_config.hbm_budget_bytes - pool_bytes,
                        d_model=config.d_model,
                        expert_d_ff=config.expert_d_ff // config.expert_tp,
                        num_layers=config.num_layers,
                        bytes_per_param=dtype_bytes,
                    ),
                ),
            )
        if engine_config.shed.enabled and (
            profile is None
            or not config.is_moe
            or engine_config.replication.replica_slots <= 0
        ):
            raise ValueError(
                "EngineConfig(shed.enabled=True) needs a MoE config, an "
                "attached VariabilityProfile, and a replicated pool "
                "(replication.replica_slots > 0) — overflow tokens can "
                "only re-seat on a live replica row, and the shed-vs-wait "
                "gate prices against the profile's staircase curves"
            )
        if engine_config.online and (profile is None or not config.is_moe):
            raise ValueError(
                "EngineConfig(online=True) needs a MoE config and an attached "
                "VariabilityProfile — without them no adaptation plane can "
                "run and the engine would silently never replan"
            )
        if engine_config.replication.replica_slots > 0 and (
            profile is None
            or not config.is_moe
            or engine_config.placement_policy != "gem"
        ):
            raise ValueError(
                "EngineConfig(replication.replica_slots>0) needs a MoE "
                "config, an attached VariabilityProfile, and the gem "
                "placement policy — the replica split is speed-proportional "
                "and only the gem planner is replication-aware"
            )
        self.params = params
        self.config = config
        self.policy = policy
        self.ecfg = engine_config
        # Telemetry hub — always constructed: the registry is the single
        # source of truth for jit trace counts and migration records even
        # with telemetry=None (a disabled hub records no span/instant
        # events, so the default run is bit-identical to an uninstrumented
        # one — all instruments are pure host-side Python state). The
        # clock binds to the simulated time the engine advances.
        self.telemetry = (
            telemetry if telemetry is not None else Telemetry(enabled=False)
        )
        # the clock must be readable during __init__ itself: the online
        # controller's audit.init instant stamps it at construction
        self.sim_time = 0.0
        self.telemetry.set_clock(lambda: self.sim_time)
        self.scheduler = Scheduler(
            engine_config.max_batch,
            admit_lookahead=engine_config.admit_lookahead,
            ttft_slo_s=engine_config.ttft_slo_s,
        )
        self.scheduler.telemetry = self.telemetry
        self.step_count = 0
        self._uid = 0
        self.finished: list[Request] = []
        # live-traffic state: pending timestamped arrivals (serve()) and
        # which decode slots hold an installed (prefilled) request
        self.arrivals: deque[RequestSpec] = deque()
        self.installed = np.zeros(engine_config.max_batch, dtype=bool)
        self.kv_pool: PagedKVPool | None = None
        self.preemption_count = 0

        # GEM control plane (MoE archs only)
        self.profile = profile
        self.true_profile: VariabilityProfile | None = None  # harness-injected
        # ground truth when it departs the believed profile (set_true_profile)
        self.planner: GEMPlanner | None = None
        self.controller: OnlineController | None = None
        self._migrate: MigrationExecutable | None = None
        self._collective_axis: str | None = None
        # per-step straggler attribution (load vs variability split) —
        # populated on MoE engines with a profile; see latency_report()
        self.attribution: AttributionAccumulator | None = None
        # per-step placement regret vs the hindsight oracle — same gating
        self.regret: RegretTracker | None = None
        # capacity-overflow shedding: (L,) int32 enable flags for the NEXT
        # step's dispatch pass (None ⇒ plane off and the decode operand is
        # the empty pytree — program identical to the pre-shed engine)
        self._shed_enables: np.ndarray | None = None
        self._shed_token_bytes = 0.0
        self._shed_total = 0
        self._shed_overflow_total = 0
        self._shed_saved_s = 0.0
        self._shed_transfer_s = 0.0
        self.placement_applied = False
        self.placements = None
        self.current_placements: list[Placement] | None = None
        self.current_rplacements: list[ReplicatedPlacement] | None = None
        if profile is not None:
            # Scheduler admission tracks the profiled fleet: the slowest
            # device's relative throughput scales the prefill token budget
            # so admission bursts don't amplify the straggler.
            self.scheduler.set_slow_device_factor(
                float(profile.relative_speed().min())
            )
        if config.is_moe:
            nd = num_devices or (profile.num_devices if profile else 4)
            if (
                engine_config.migration_via == "collective"
                and policy.mesh is not None
                and policy.model_axis_size > 1
                and nd != policy.model_axis_size
            ):
                # the collective plane shards rows over the model axis, the
                # cost model prices locality by placement device — when the
                # two disagree, a "cross-device" move can be a same-shard
                # copy (or vice versa) and measured traffic stops matching
                # the model's accounting (it stays correct, just unmatched)
                warnings.warn(
                    f"migration_via='collective': placement device count "
                    f"{nd} != model-axis size {policy.model_axis_size}; "
                    "measured migration traffic will not match the cost "
                    "model's cross-device accounting",
                    RuntimeWarning,
                    stacklevel=2,
                )
            self.planner = GEMPlanner(
                config.num_experts * config.expert_tp,
                nd,
                config.num_layers,
                engine_config.gem,
            )
            self.attribution = AttributionAccumulator(nd)
            self.regret = RegretTracker(
                config.num_experts * config.expert_tp, nd
            )
            if profile is not None:
                self.planner.set_profile(profile)
            self.placements = self._device_tables(
                identity_placement(config, config.num_layers)
            )
            Ev = config.num_experts * config.expert_tp
            self.current_placements = [
                Placement.linear(Ev, nd) for _ in range(config.num_layers)
            ]
            if engine_config.replication.replica_slots > 0:
                # install the replicated weight pool up front (linear layout
                # padded with per-device local copies) so the slot count is
                # a run constant and online migrations never resize it
                self.current_rplacements = [
                    ReplicatedPlacement.linear(
                        Ev, nd, engine_config.replication.replica_slots,
                        profile=profile, config=engine_config.replication,
                    )
                    for _ in range(config.num_layers)
                ]
                self._install_replicated_pool(self.current_rplacements)
            # schedule-generic migration executable: one jit, traced once,
            # whose (L, S) row-source map is an operand — every migration
            # batch (any swap set, any layer subset, mid-run) reuses the
            # compiled program. Collective when the policy has a live
            # expert sharding axis; the host gather (bit-identical)
            # otherwise.
            num_slots = int(self.params["blocks"]["moe"]["w_gate"].shape[1])
            self._collective_axis = None
            if engine_config.migration_via == "collective":
                self._collective_axis = policy.expert_collective_axis(
                    num_slots
                )
            self._migrate = MigrationExecutable(
                mesh=policy.mesh if self._collective_axis else None,
                axis=self._collective_axis or "model",
                telemetry=self.telemetry,
            )
            # one cost model for both replan paths: the online plane prices
            # its batches with it, and the one-shot swap charges the same
            # model so the two modes' latency reports stay comparable
            dtype_bytes = jax.tree.leaves(params)[0].dtype.itemsize
            Fv = config.expert_d_ff // config.expert_tp
            self._cost_model = engine_config.migration.cost_model_for_dims(
                config.d_model, Fv, bytes_per_param=dtype_bytes
            )
            if engine_config.shed.enabled:
                # all layers start disabled: step t's measured overflow
                # prices step t+1's enables (one step behind, by design)
                self._shed_enables = np.zeros(
                    config.num_layers, dtype=np.int32
                )
                self._shed_token_bytes = (
                    float(engine_config.shed.token_bytes)
                    if engine_config.shed.token_bytes is not None
                    else default_token_bytes(config.d_model, dtype_bytes)
                )
                # the decode clamp the gate pricing must predict exactly:
                # same formula build_dispatch applies per data group
                gd = (
                    policy.data_axis_size if policy.mesh is not None else 1
                )
                self._shed_capacity = slot_capacity(
                    max(engine_config.max_batch // max(gd, 1), 1),
                    config,
                    capacity_factor=config.decode_capacity_factor,
                    num_slots=num_slots,
                    replicated=True,
                )
            if engine_config.online and profile is not None:
                self.controller = OnlineController(
                    self.planner,
                    self._cost_model,
                    OnlineConfig(
                        policy=engine_config.placement_policy,
                        online=True,
                        drift=engine_config.drift,
                        migration=engine_config.migration,
                        replication=engine_config.replication,
                        replan_cooldown=engine_config.replan_cooldown,
                        payback_horizon=engine_config.payback_horizon,
                        staggered_replan=engine_config.staggered_replan,
                    ),
                    initial_placements=self.current_placements,
                    initial_rplacements=self.current_rplacements,
                    telemetry=self.telemetry,
                )

        # simulated latency accounting (sim_time itself initialized above,
        # before the telemetry clock bind)
        self.sim_step_latencies: list[float] = []

        # migration data-plane accounting (one record per applied batch —
        # the cost model's charge next to what the executed collective
        # schedule actually shipped; fig22's measured-vs-modeled gate) now
        # lives on the telemetry hub; ``migration_records`` is a property
        # read-through so no caller breaks
        self.true_interconnect: Any | None = None  # MigrationCostModel

        # decode cache pool (same storage dtype as the params)
        cache_dtype = jax.tree.leaves(params)[0].dtype
        self.cur_len = np.zeros(engine_config.max_batch, dtype=np.int32)
        self.last_token = np.zeros(engine_config.max_batch, dtype=np.int32)
        self.block_tables: np.ndarray | None = None
        if self.paged:
            self.kv_pool = PagedKVPool(
                self._kv_num_blocks, block_size,
                watermark_blocks=engine_config.kv.watermark_blocks,
            )
            self.kv_pool.telemetry = self.telemetry
            self.caches = init_paged_decode_cache(
                config, self._kv_num_blocks, block_size, policy,
                dtype=cache_dtype,
            )
            # (B, n_max) attention-side view; null-block rows for idle slots
            self.block_tables = np.zeros(
                (engine_config.max_batch, self._n_max), dtype=np.int32
            )
            def _decode_paged(params, caches, cur_len, tables, tokens,
                              placements, shed):
                # python side effect: runs once per trace, never on
                # compiled-executable reuse
                self.telemetry.counter("jit.trace.decode").inc()
                return decode_step(
                    params, caches, cur_len, tokens, config, policy,
                    placements, block_tables=tables,
                    decode_mode=engine_config.decode_mode,
                    shed_enables=shed,
                )

            # the pools are donated, so each step writes its tokens into
            # the buffers it was given
            donate = _donate_caches()
            self._decode = jax.jit(
                _decode_paged, donate_argnums=(1,) if donate else ()
            )

            def _install(pool, new, blocks):
                # new (L, 1, P, KV, hd): flatten the heads into the pool's
                # lane-dense KV·hd dim, pad P up to n·bs, reshape to
                # blocks, scatter into the pool rows this request owns
                L, _, P = new.shape[:3]
                n = blocks.shape[0]
                newp = jnp.pad(
                    new[:, 0].reshape(L, P, pool.shape[-1]),
                    ((0, 0), (0, n * block_size - P), (0, 0)),
                ).reshape(L, n, block_size, pool.shape[-1])
                return pool.at[:, blocks].set(newp)

            self._paged_install = jax.jit(
                _install, donate_argnums=(0,) if donate else ()
            )
        else:
            self.caches = init_decode_cache(
                config, engine_config.max_batch, engine_config.max_len,
                policy, dtype=cache_dtype,
            )
            def _decode_dense(params, caches, cur_len, tokens, placements,
                              shed):
                self.telemetry.counter("jit.trace.decode").inc()
                return decode_step(
                    params, caches, cur_len, tokens, config, policy,
                    placements, decode_mode=engine_config.decode_mode,
                    shed_enables=shed,
                )

            self._decode = jax.jit(_decode_dense)

        def _prefill_fn(params, batch, placements):
            self.telemetry.counter("jit.trace.prefill").inc()
            return prefill(params, batch, config, policy, placements)

        self._prefill = jax.jit(_prefill_fn)

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, *,
               arrival_time: float | None = None, task: str = "") -> int:
        prompt = np.asarray(prompt, np.int32)
        if self.kv_pool is not None:
            total = int(prompt.shape[0]) + int(max_new_tokens)
            need = self.kv_pool.blocks_for(total)
            if need > self.kv_pool.usable_blocks:
                raise ValueError(
                    f"request needs {need} KV blocks but the pool only has "
                    f"{self.kv_pool.usable_blocks} — it could never be "
                    "served (grow PagedKVConfig.num_blocks or shorten it)"
                )
        self._uid += 1
        req = Request(
            self._uid, prompt, max_new_tokens,
            arrival_step=self.step_count, task=task,
        )
        req.arrival_time = (
            self.sim_time if arrival_time is None else float(arrival_time)
        )
        self.scheduler.submit(req)
        return self._uid

    def serve(self, specs: Iterable[RequestSpec], *, max_steps: int = 100_000
              ) -> list[Request]:
        """Run a timestamped arrival stream to completion.

        Requests enter the scheduler queue when the simulated clock
        reaches their ``arrival_time``; when the engine is idle the clock
        jumps to the next arrival. ``submit()+run()`` is the degenerate
        all-at-``t=0`` case of this path.
        """
        merged = sorted(
            list(self.arrivals) + list(specs),
            key=lambda s: s.arrival_time,  # stable: ties keep list order
        )
        self.arrivals = deque(merged)
        steps = 0
        while (self.arrivals or self.scheduler.has_work()) \
                and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    def _ingest_arrivals(self) -> None:
        """Move arrivals whose timestamp has passed into the queue; jump
        the clock forward when the engine is otherwise idle."""
        if self.arrivals and not self.scheduler.has_work():
            self.sim_time = max(
                self.sim_time, self.arrivals[0].arrival_time
            )
        while self.arrivals and \
                self.arrivals[0].arrival_time <= self.sim_time:
            spec = self.arrivals.popleft()
            self.submit(
                spec.prompt, spec.max_new_tokens,
                arrival_time=spec.arrival_time, task=spec.task,
            )

    # ------------------------------------------------------------------
    def _write_slot(self, slot: int, req: Request) -> None:
        """Prefill one request and install its caches into the pool slot."""
        with self.telemetry.span("engine.prefill", step=self.step_count,
                                 uid=req.uid, tokens=req.prompt_len):
            batch = {"tokens": jnp.asarray(req.prompt[None, :])}
            logits, caches = self._prefill(self.params, batch, self.placements)
            L = req.prompt_len

            def install(pool, new):
                # pool (..., max_batch, S_pool, ...), new (..., 1, L, ...); the
                # leading layer dims match — write [slot, :L].
                if pool.ndim == new.ndim and new.shape[-3:] == pool.shape[-3:]:
                    return pool.at[..., slot, :, :, :].set(new[..., 0, :, :, :])
                return pool

            # attention caches: (L?, B, S, KV, hd) — pad new to pool length
            def install_attn(pool, new):
                pad = pool.shape[-3] - new.shape[-3]
                new = jnp.pad(
                    new, [(0, 0)] * (new.ndim - 3) + [(0, pad), (0, 0), (0, 0)]
                )
                idx = (slice(None),) * (new.ndim - 4) + (slot,)
                return pool.at[idx].set(new[..., 0, :, :, :])

            c = self.caches
            if "attn" in c:
                c["attn"]["k"] = install_attn(c["attn"]["k"], caches["attn"]["k"])
                c["attn"]["v"] = install_attn(c["attn"]["v"], caches["attn"]["v"])
            for key in ("ssm", "ssm_staged", "ssm_tail"):
                if key in c:
                    for part in c[key]:
                        pool, new = c[key][part], caches[key][part]
                        bdim = pool.ndim - new.ndim + 1  # batch axis in pool
                        idx = (slice(None),) * (new.ndim - (pool.ndim - bdim) - 1)
                        # batch axis position: state (..., B, nh, hd, N) → -4;
                        # conv (..., B, cw-1, C) → -3
                        if part == "state":
                            c[key][part] = pool.at[..., slot, :, :, :].set(
                                new[..., 0, :, :, :]
                            )
                        else:
                            c[key][part] = pool.at[..., slot, :, :].set(
                                new[..., 0, :, :]
                            )
            self.cur_len[slot] = req.prompt_len
            self.last_token[slot] = int(np.asarray(jnp.argmax(logits[0])))
            self.installed[slot] = True

    def _install_paged_slot(self, slot: int, req: Request) -> None:
        """Prefill one request and scatter its KV into its owned blocks."""
        with self.telemetry.span("engine.prefill", step=self.step_count,
                                 uid=req.uid, tokens=req.prompt_len):
            batch = {"tokens": jnp.asarray(req.prompt[None, :])}
            logits, caches = self._prefill(self.params, batch, self.placements)
            table = self.kv_pool.block_table(req.uid)
            blocks = jnp.asarray(np.asarray(table, np.int32))
            c = self.caches["attn"]
            c["k"] = self._paged_install(c["k"], caches["attn"]["k"], blocks)
            c["v"] = self._paged_install(c["v"], caches["attn"]["v"], blocks)
            self.block_tables[slot, :] = 0
            self.block_tables[slot, : len(table)] = table
            self.cur_len[slot] = req.prompt_len
            self.last_token[slot] = int(np.asarray(jnp.argmax(logits[0])))
            self.installed[slot] = True

    def _prefill_phase(self) -> float:
        """Advance prefill for admitted-but-uninstalled slots; returns the
        simulated prefill time charged to this step.

        With ``prefill_chunk=0`` a request prefills atomically in its
        admission step (the legacy behaviour). With a positive chunk the
        *simulated* cost is spread over ``ceil(P/chunk)`` steps — decode
        for already-installed slots interleaves with this accounting — and
        the prefill kernel runs once, when the last chunk lands.
        """
        chunk = self.ecfg.prefill_chunk
        charge = 0.0
        advanced = 0
        installed_now: list[Request] = []
        for slot, req in sorted(self.scheduler.active.items()):
            if self.installed[slot]:
                continue
            advance = req.prompt_len - req.prefill_progress
            if chunk > 0:
                advance = min(advance, chunk)
            req.prefill_progress += advance
            advanced += advance
            charge += advance * self.ecfg.prefill_time_per_token
            if req.prefilled:
                if self.paged:
                    self._install_paged_slot(slot, req)
                else:
                    self._write_slot(slot, req)
                installed_now.append(req)
        self.sim_time += charge
        if advanced > 0:
            self.telemetry.counter("engine.prefill_tokens").inc(advanced)
            self.telemetry.emit_span(
                "prefill", self.sim_time - charge, charge, tokens=advanced
            )
        for req in installed_now:
            if req.first_token_time < 0:  # keep TTFT across preemptions
                req.first_token_time = self.sim_time
        return charge

    def _kv_admit(self, req: Request) -> bool:
        """Scheduler admission gate: reserve the prompt's KV blocks.

        Admission holds only the *prompt* blocks (decode growth allocates
        on demand, preempting under pressure) but keeps the configured
        watermark free as a growth reserve.
        """
        if not self.kv_pool.can_allocate(req.prompt_len):
            return False
        return self.kv_pool.allocate(req.uid, req.prompt_len)

    def _preempt(self, slot: int, req: Request) -> None:
        """Evict a running request: free its blocks, requeue it at the
        head, and recompute its tokens on re-admission (greedy decode
        regenerates them bit-identically)."""
        self.kv_pool.release(req.uid)
        self.scheduler.release(slot)
        req.generated.clear()
        req.preemptions += 1
        self.preemption_count += 1
        self.telemetry.counter("engine.preemptions").inc()
        self.telemetry.instant("preempt", request=req.uid)
        self.scheduler.requeue_front(req)
        self.installed[slot] = False
        self.cur_len[slot] = 0
        self.last_token[slot] = 0
        self.block_tables[slot, :] = 0

    def _ensure_decode_capacity(self) -> None:
        """Grow each running row's block table to cover this step's write;
        when the pool runs dry, preempt the youngest-arrival request
        (FCFS protects the oldest) and retry."""
        for slot in list(np.nonzero(self.installed)[0]):
            req = self.scheduler.active.get(int(slot))
            if req is None:
                continue
            want = int(self.cur_len[slot]) + 1
            while not self.kv_pool.allocate(req.uid, want):
                victims = sorted(
                    (
                        (s, r) for s, r in self.scheduler.active.items()
                        if self.installed[s]
                    ),
                    key=lambda sr: (sr[1].arrival_time, sr[1].uid),
                    reverse=True,
                )
                if not victims:
                    raise RuntimeError("KV pool dry with no one to preempt")
                vslot, victim = victims[0]
                self._preempt(vslot, victim)
                if victim is req:
                    break  # evicted itself: row is no longer runnable
            else:
                table = self.kv_pool.block_table(req.uid)
                self.block_tables[slot, : len(table)] = table

    # ------------------------------------------------------------------
    @property
    def jit_trace_counts(self) -> dict[str, int]:
        """Traces per jitted entry point: ``decode``, ``prefill``,
        ``migrate``. Under ``decode_mode="scan"`` the contract is one
        decode trace per (mode, shapes) signature and **zero** new
        traces when a migration applies — the fig24 CI gate. Thin
        read-through of the telemetry registry's ``jit.trace.*``
        counters (the single source of truth)."""
        reg = self.telemetry.registry
        return {
            "decode": int(reg.counter("jit.trace.decode").value),
            "prefill": int(reg.counter("jit.trace.prefill").value),
            "migrate": int(reg.counter("jit.trace.migrate").value),
        }

    @property
    def migration_records(self) -> list[dict[str, Any]]:
        """One record per applied migration batch — thin read-through of
        the telemetry hub's record list (the single source of truth)."""
        return self.telemetry.migration_records

    def _apply_migration_sources(
        self, src: np.ndarray, *, swap_tables: bool
    ) -> list:
        """Rewrite the stacked expert pool through the schedule-generic
        executable: one compiled call for the whole (L, S) row-source
        operand, no per-layer jits, no retracing. With ``swap_tables``
        the (L, E_v) router tables swap on device in the same dispatch
        (permutation batches only) and ``self.placements`` follows.
        Returns per-layer :class:`CollectiveStats` (empty when the
        collective plane isn't live — host applies carry no measurement).
        """
        moe = dict(self.params["blocks"]["moe"])
        tables = self.placements if swap_tables else None
        (wg, wu, wd), new_tables = self._migrate(
            src, tables, moe["w_gate"], moe["w_up"], moe["w_down"]
        )
        moe["w_gate"], moe["w_up"], moe["w_down"] = wg, wu, wd
        new_blocks = dict(self.params["blocks"])
        new_blocks["moe"] = moe
        self.params = {**self.params, "blocks": new_blocks}
        if swap_tables:
            self.placements = new_tables
        if self._collective_axis is None:
            return []
        row_bytes = sum(
            int(np.prod(w.shape[2:])) * w.dtype.itemsize
            for w in (wg, wu, wd)
        )
        return [
            s for _, s in stats_for_dense_sources(
                src, self.policy.model_axis_size, row_bytes
            )
        ]

    def _replica_tables(self, rplacements) -> jnp.ndarray:
        """(L, E_v, P) replica-split router tables for the data plane."""
        P = self.ecfg.replication.pattern_period
        return self._device_tables(
            np.stack([rp.replica_table(P) for rp in rplacements])
        )

    def _device_tables(self, tables) -> jax.Array:
        """Router tables as decode operands: replicated over the policy's
        mesh, the sharding migration-swapped tables come back with — an
        operand whose sharding changed would retrace the decode step."""
        if self.policy.mesh is None:
            return jnp.asarray(tables)
        return jax.device_put(
            tables, self.policy.named(*(None,) * np.ndim(tables))
        )

    def _install_replicated_pool(self, rplacements) -> None:
        """Expand the virtual-ordered expert weights into the replicated
        slot pool: row ``s`` ← virtual expert ``slot_to_expert[s]`` (the
        same gather ``apply_placement`` performs, with repeated indices).
        Only valid while the pool is still in virtual order (engine init)."""
        s2e = jnp.asarray(
            np.stack([rp.slot_to_expert for rp in rplacements])
        )
        new_blocks = dict(self.params["blocks"])
        new_blocks["moe"] = apply_placement(self.params["blocks"]["moe"], s2e)
        self.params = {**self.params, "blocks": new_blocks}
        self.placements = self._replica_tables(rplacements)

    def _retarget_replicated_pool(self, rplacements) -> list:
        """Move the live replicated pool to new layouts in one parallel row
        gather per layer (each target slot reads any current copy of its
        expert); the caller prices the install via ``replica_fetch_rows``.
        Under ``migration_via="collective"`` each layer's gather executes
        as one-row ppermute broadcasts instead; returns the executed
        schedules' :class:`~repro.kernels.collective.CollectiveStats`
        (empty on the host path)."""
        assert self.current_rplacements is not None
        if self._collective_axis is not None:
            # two-phase install: one interconnect fetch per (device, new
            # expert), then local HBM fan-out — the traffic
            # replica_fetch_rows models, exactly. Each phase is one dense
            # (L, S) operand through the schedule-generic executable.
            spd = rplacements[0].slots_per_device
            fetch, fanout = [], []
            for cur, new in zip(self.current_rplacements, rplacements):
                f1, f2 = replica_install_phases(
                    cur.slot_layout(), new.slot_layout(), spd
                )
                fetch.append(f1)
                fanout.append(f2)
            stats = self._apply_migration_sources(
                np.stack(fetch).astype(np.int32), swap_tables=False
            )
            stats += self._apply_migration_sources(
                np.stack(fanout).astype(np.int32), swap_tables=False
            )
        else:
            srcs = np.stack([
                replica_source_permutation(
                    cur.slot_layout(), new.slot_layout()
                )
                for cur, new in zip(self.current_rplacements, rplacements)
            ])
            stats = self._apply_migration_sources(
                srcs.astype(np.int32), swap_tables=False
            )
        self.placements = self._replica_tables(rplacements)
        return stats

    def set_true_profile(self, profile: VariabilityProfile | None) -> None:
        """Inject the *actual* fleet behaviour when it departs the believed
        profile (mid-run power cap, thermal throttling). Simulated latencies
        come from this ground truth; the control plane keeps planning on its
        belief until its variability-drift detector repairs it — on real
        hardware the same gap appears between wall-clock and the stale
        profile with no injection needed."""
        self.true_profile = profile

    def set_true_interconnect(
        self, bandwidth: float, base_overhead: float | None = None
    ) -> None:
        """Inject the *actual* interconnect when it departs the cost
        model's configured assumption (a mis-specified fabric, a congested
        link). Measured migration times then come from this ground truth
        while the controller keeps pricing with its believed bandwidth —
        until its :class:`~repro.core.latency_model.BandwidthEstimator`
        learns the real one from the measurements (with
        ``MigrationConfig.calibrate_bandwidth``). On real hardware the gap
        appears between wall-clock transfer timers and the config, no
        injection needed."""
        self.true_interconnect = dataclasses.replace(
            self._cost_model,
            bandwidth=float(bandwidth),
            base_overhead=(
                self._cost_model.base_overhead
                if base_overhead is None
                else float(base_overhead)
            ),
        )

    @property
    def _measure_interconnect(self):
        """The interconnect that times executed collective batches: the
        injected ground truth, else the believed model."""
        if self.true_interconnect is not None:
            return self.true_interconnect
        return (
            self.controller.cost_model
            if self.controller is not None
            else self._cost_model
        )

    @property
    def _sim_profile(self) -> VariabilityProfile | None:
        return self.true_profile if self.true_profile is not None else self.profile

    def _step_cost_matrix(self, counts_virt: np.ndarray) -> np.ndarray | None:
        """(L, G) per-layer per-device latencies of this step, ground truth.

        Replica-aware: with a replicated pool the per-device loads come from
        the speed-proportional split, not a one-hot placement."""
        if self._sim_profile is None or self.current_placements is None:
            return None
        if self.current_rplacements is not None:
            return replicated_step_cost_matrix(
                counts_virt, self._sim_profile, self.current_rplacements
            )
        return step_cost_matrix(
            counts_virt, self._sim_profile, self.current_placements
        )

    def _step_token_matrix(self, counts_virt: np.ndarray) -> np.ndarray | None:
        """(L, G) per-layer per-device token loads of this step — the
        straggler-attribution input, replica-split aware."""
        if self._sim_profile is None or self.current_placements is None:
            return None
        G = self._sim_profile.num_devices
        if self.current_rplacements is not None:
            return replicated_step_token_matrix(
                counts_virt, G, self.current_rplacements
            )
        return step_token_matrix(counts_virt, G, self.current_placements)

    def _shed_operand(self):
        """The decode executable's (L,) shed-enable operand — ``None``
        when the plane is off, so the traced program (and therefore
        ``jit_trace_counts``) is byte-identical to the pre-shed engine."""
        if self._shed_enables is None:
            return None
        return jnp.asarray(self._shed_enables)

    def _shed_step(
        self,
        counts_virt: np.ndarray,
        moe_aux,
        cost_mx: np.ndarray | None,
    ) -> float | None:
        """Per-step shed accounting + next step's gate pricing.

        Returns the shed-*adjusted* straggler latency the simulated fleet
        actually paid this step (including the interconnect transfer
        charge), or ``None`` when nothing shed — the caller then falls
        back to the legacy ``cost_mx`` charge. Crucially the legacy
        matrix itself is what the controller, the straggler attribution,
        and the regret oracle keep seeing: shedding masks the symptom
        for *this* step's latency only, so placement replans keep
        targeting the underlying imbalance (compose, don't compete —
        ROADMAP direction 1).
        """
        tel = self.telemetry
        overflow = np.asarray(moe_aux.overflow_tokens, dtype=np.int64)
        shed_tok = np.asarray(moe_aux.shed_tokens, dtype=np.int64)
        shed_delta = np.asarray(moe_aux.shed_delta, dtype=np.int64)  # (L, S)
        total_over = int(overflow.sum())
        total_shed = int(shed_tok.sum())
        self._shed_overflow_total += total_over
        if total_over:
            tel.counter("shed.overflow_tokens").inc(total_over)

        adjusted: float | None = None
        prof = self._sim_profile
        if (
            total_shed > 0
            and prof is not None
            and cost_mx is not None
            and self.current_rplacements is not None
        ):
            tokens = self._step_token_matrix(counts_virt)  # un-shed (L, G)
            spd = self.current_rplacements[0].slots_per_device
            adj_mx = shed_adjusted_step_cost_matrix(
                tokens, shed_delta, prof, spd
            )
            # the actual transfer is charged at the measuring
            # interconnect's bandwidth (injected ground truth when the
            # harness departs the believed model) — same accounting rule
            # as migration batches. Only rows that change *device* touch
            # the interconnect: a re-seat between two slots of the same
            # device (the local-copy pool at engine init) is free.
            cross_rows = float(
                np.maximum(
                    shed_device_deltas(shed_delta, spd), 0.0
                ).sum()
            )
            transfer_s = (
                cross_rows * self._shed_token_bytes
                / self._measure_interconnect.bandwidth
            )
            legacy = float(cost_mx.max(axis=1).sum())
            adjusted = float(adj_mx.max(axis=1).sum()) + transfer_s
            self._shed_total += total_shed
            self._shed_transfer_s += transfer_s
            self._shed_saved_s += legacy - adjusted
            tel.counter("shed.tokens").inc(total_shed)
            tel.counter("shed.steps").inc()
            tel.counter("shed.transfer_s").inc(transfer_s)
            tel.gauge("shed.saved_s").set(self._shed_saved_s)
            if tel.enabled:
                recv_dev = np.maximum(
                    shed_device_deltas(shed_delta, spd), 0.0
                ).sum(axis=0)  # (G,) assignments received per device
                total_recv = float(recv_dev.sum())
                for g in range(recv_dev.shape[0]):
                    if recv_dev[g] <= 0:
                        continue
                    tel.emit_span(
                        "shed.recv", self.sim_time,
                        transfer_s * float(recv_dev[g]) / total_recv,
                        track=f"device{g}", step=self.step_count,
                        tokens=int(recv_dev[g]),
                    )

        # price the NEXT step's enables from this step's overflow — one
        # step behind by construction, with the *believed* profile and
        # bandwidth (the controller's beliefs tighten over time when
        # bandwidth calibration is on)
        if self.controller is not None:
            enables = self.controller.shed_decisions(
                counts_virt, overflow,
                token_bytes=self._shed_token_bytes,
                capacity=self._shed_capacity,
                min_overflow=self.ecfg.shed.min_overflow,
                hysteresis=self.ecfg.shed.hysteresis,
                drop_penalty_s=self.ecfg.shed.drop_penalty_s,
            )
        else:
            # one-shot engines price with the believed profile and the
            # configured cost model directly (no calibration loop)
            enables = shed_gate_decisions(
                counts_virt, self.current_rplacements, self.profile,
                self._shed_capacity,
                bandwidth=self._cost_model.bandwidth,
                token_bytes=self._shed_token_bytes,
                min_overflow=self.ecfg.shed.min_overflow,
                hysteresis=self.ecfg.shed.hysteresis,
                drop_penalty_s=self.ecfg.shed.drop_penalty_s,
            )
        self._shed_enables = np.asarray(enables, dtype=np.int32)
        return adjusted

    def _observe_attribution(self, counts_virt: np.ndarray) -> None:
        """Decompose this step's straggler slack into load vs variability
        (repro.telemetry.attribution) and fold it into the run aggregate +
        registry metrics. Host-side numpy only — never touches tokens."""
        prof = self._sim_profile
        tokens = self._step_token_matrix(counts_virt)
        if prof is None or tokens is None or self.attribution is None:
            return
        att = attribute_step(tokens, prof)
        self.attribution.observe(att)
        tel = self.telemetry
        # slack_total/slack_load are max−mean ⇒ non-negative (counters);
        # the variability residual can be negative (fast devices carrying
        # the extra tokens), so its cumulative sum rides a gauge
        tel.counter("attr.slack_total_s").inc(att.total)
        tel.counter("attr.slack_load_s").inc(att.load)
        tel.gauge("attr.slack_var_s").set(self.attribution.sum_var)
        tel.histogram("attr.step_slack_s", _ATTR_SLACK_BOUNDS).observe(
            att.total
        )
        if tel.enabled:
            cost = prof.cost_all(tokens)  # (L, G)
            device_time = cost.sum(axis=0)
            straggler = int(device_time.argmax())
            for g in range(cost.shape[1]):
                tel.emit_span(
                    "expert_compute", self.sim_time, float(device_time[g]),
                    track=f"device{g}", step=self.step_count,
                    straggler=(g == straggler),
                )

    def _observe_regret(
        self, counts_virt: np.ndarray, cost_mx: np.ndarray | None
    ) -> None:
        """Fold this step into the placement-regret aggregate
        (repro.telemetry.regret) + registry metrics. Host-side numpy only
        — like attribution, never touches tokens."""
        prof = self._sim_profile
        if prof is None or cost_mx is None or self.regret is None:
            return
        # migration-lag when the control plane has already committed but
        # not landed: controller mid-adaptation, or the one-shot plan not
        # yet applied — a replan now could not reach the oracle sooner
        lagging = (
            self.controller.adapting
            if self.controller is not None
            else not self.placement_applied
        )
        sr = self.regret.observe(
            counts_virt,
            prof,
            float(cost_mx.max(axis=1).sum()),
            placements=(
                None
                if self.current_rplacements is not None
                else self.current_placements
            ),
            lagging=lagging,
        )
        record_step_metrics(
            self.telemetry, sr, self.step_count,
            search_rounds=self.regret.last_search_rounds,
        )

    def _maybe_replan(self) -> None:
        """The one-shot plan, once its trace window has filled."""
        if self._replan_due():
            # step() has already counted the step this call ends
            with self.telemetry.span("engine.replan",
                                     step=self.step_count - 1):
                self._replan_once()

    def _replan_due(self) -> bool:
        if (
            self.planner is None
            or self.controller is not None  # online mode: drift, not a timer
            or self.placement_applied
            or self.profile is None
        ):
            return False
        threshold = (
            self.ecfg.replan_after
            if self.ecfg.replan_after is not None
            else self.ecfg.gem.trace_length
        )
        return self.step_count >= threshold and all(
            c.num_steps >= self.ecfg.gem.trace_length
            for c in self.planner.collectors
        )

    def _replan_once(self) -> None:
        if self.ecfg.placement_policy == "linear":
            self.placement_applied = True
            return
        if self.ecfg.placement_policy == "eplb":
            from ..core.eplb import eplb_placement

            placements = [
                eplb_placement(
                    c.trace(self.ecfg.gem.trace_length), self.profile.num_devices
                )
                for c in self.planner.collectors
            ]
        elif self.ecfg.replication.replica_slots > 0:
            # replication-aware plan: new copies of the hot consistent
            # experts land as one-row broadcasts; price the rows each
            # device must fetch over the interconnect
            results = plan_replicated_layers(
                self.planner, self.ecfg.replication
            )
            rplacements = [r.placement for r in results]
            moves = sum(
                replica_fetch_rows(cur, new)
                for cur, new in zip(self.current_rplacements, rplacements)
            )
            # audited: the retarget decision's inputs (live + target
            # layouts) ride the event so decision_replay can re-derive
            # the priced move count from the log alone
            self.telemetry.instant(
                "audit.retarget",
                track="controller",
                step=self.step_count,
                num_experts=int(self.planner.num_experts),
                num_devices=int(self.profile.num_devices),
                slot_layouts=[
                    rp.slot_layout().tolist()
                    for rp in self.current_rplacements
                ],
                target_layouts=[
                    rp.slot_layout().tolist() for rp in rplacements
                ],
                moves=int(moves),
                modeled_s=float(self._cost_model.cost(moves)),
            )
            with self.telemetry.span("engine.migrate",
                                     step=self.step_count - 1, moves=moves):
                stats = self._retarget_replicated_pool(rplacements)
                swap_cost = self._record_migration(
                    moves, self._cost_model.cost(moves), stats, None
                )
            if self.sim_step_latencies:
                self.sim_step_latencies[-1] += swap_cost
            self.sim_time += swap_cost
            self.current_rplacements = rplacements
            self.placement_applied = True
            return
        else:
            placements = self.planner.plan().placements
        # Step-4: permute expert weights + swap router remap tables — one
        # call through the schedule-generic executable (the pool is still
        # in virtual order here, so each layer's row-source map IS its
        # slot_to_expert table, and the in-dispatch table swap inverts it
        # into expert_to_slot)
        slot_to_expert = np.stack([p.slot_to_expert() for p in placements])
        moves = sum(
            len(cur.moved_slots(new))
            for cur, new in zip(self.current_placements, placements)
        )
        with self.telemetry.span("engine.migrate",
                                 step=self.step_count - 1, moves=moves):
            stats = self._apply_migration_sources(
                slot_to_expert.astype(np.int32), swap_tables=True
            )
            # the one-shot swap moves weights too: charge it to the step
            # that performs it (unbudgeted, one batch), with the same cost
            # model the online mode pays per batch — otherwise comparing
            # the two modes' latency reports silently favours one-shot
            swap_cost = self._record_migration(
                moves, self._cost_model.cost(moves), stats, None
            )
        if self.sim_step_latencies:
            self.sim_step_latencies[-1] += swap_cost
        self.sim_time += swap_cost
        self.current_placements = placements
        self.placement_applied = True

    # ------------------------------------------------------------------
    def _online_step(
        self, counts_virt: np.ndarray, cost_mx: np.ndarray | None
    ) -> float:
        """Drive the online controller for one step; returns the migration
        cost to charge to this step's simulated latency.

        The controller sees the (L, E_v) counts plus the per-device observed
        MoE time (ground truth — the wall-clock proxy); any migration batch
        it emits is mirrored onto the stacked weights as partial per-layer
        permutations with the router tables swapped in the same step.
        """
        assert self.controller is not None
        observed = cost_mx.sum(axis=0) if cost_mx is not None else None
        decision = self.controller.observe_step(counts_virt, observed)
        migration_charge = decision.migration_cost
        if decision.migration_step is not None:
            # both batch types reduce to one dense (L, S) row-source
            # operand (a swap is {a←b, b←a}; a replica add/drop a one-row
            # broadcast) applied through the schedule-generic executable —
            # no per-batch jit, zero new traces at decode cadence. Swap
            # batches are permutations, so the router tables ride the
            # same dispatch on device; replica batches are not and keep
            # the host-side table recompute from the controller's shares.
            moves = decision.migration_step.num_moves
            with self.telemetry.span("engine.migrate", step=self.step_count,
                                     moves=moves):
                src = self.controller.dense_migration_sources(
                    decision.migration_step
                )
                stats = self._apply_migration_sources(
                    src, swap_tables=not self.controller.replicated
                )
                migration_charge = self._record_migration(
                    moves, decision.migration_cost, stats, cost_mx,
                )
            if self.controller.replicated:
                self.placements = self._device_tables(
                    self.controller.expert_to_slot_tables()
                )
                self.current_rplacements = list(
                    self.controller.current_rplacements
                )
            else:
                self.current_placements = list(
                    self.controller.current_placements
                )
        if decision.profile_rescaled:
            self.profile = self.controller.profile
            self.scheduler.set_slow_device_factor(
                float(self.profile.relative_speed().min())
            )
            if self.controller.replicated:
                # the repair recomputed every replicated expert's speed
                # shares: rebuild the split tables NOW, not at the next
                # migration batch — otherwise the data plane keeps routing
                # by the stale shares while step costs assume the new ones
                self.placements = self._device_tables(
                    self.controller.expert_to_slot_tables()
                )
                self.current_rplacements = list(
                    self.controller.current_rplacements
                )
        # "applied" must mean a planned placement actually reached the data
        # plane (a 0-move schedule counts: the plan IS the live placement) —
        # not merely that a plan existed and its migration was gate-skipped
        if self.controller.planned and any(
            r["applied"] for r in self.controller.replans
        ):
            self.placement_applied = True
        return migration_charge

    def _record_migration(
        self,
        moves: int,
        modeled_s: float,
        stats: list,
        cost_mx: np.ndarray | None,
    ) -> float:
        """Record one applied batch's measured-vs-modeled cost; returns the
        charge for the step.

        Host-path batches carry no measurement — the modeled charge stands.
        Collective batches are timed by the (possibly injected) true
        interconnect on the payload the executed schedules actually
        shipped; the double-buffered copy can hide
        ``migration.overlap_fraction`` of its transfer behind this step's
        MoE compute, so only the non-overlappable tail is charged. Every
        measurement also feeds the controller's bandwidth estimator.
        """
        record: dict[str, Any] = {
            "step": self.step_count,
            "via": self.ecfg.migration_via if stats else "host",
            "moves": int(moves),
            "modeled_s": float(modeled_s),
        }
        charge = float(modeled_s)
        tel = self.telemetry
        if stats:
            total = stats[0]
            for s in stats[1:]:
                total = total + s
            mi = self._measure_interconnect
            measured_s = mi.cost_bytes(total.payload_bytes)
            transfer_s = total.payload_bytes / mi.bandwidth
            compute_s = (
                float(cost_mx.max(axis=1).sum())
                if cost_mx is not None
                else 0.0
            )
            overlap_s = min(
                self.ecfg.migration.overlap_fraction * transfer_s, compute_s
            )
            charge = max(measured_s - overlap_s, 0.0)
            record.update(
                measured_s=float(measured_s),
                charged_s=float(charge),
                payload_bytes=int(total.payload_bytes),
                cross_rows=int(total.cross_rows),
                local_rows=int(total.local_rows),
                rounds=int(total.rounds),
                overlap_s=float(overlap_s),
            )
            tel.counter("migrate.payload_bytes").inc(
                float(total.payload_bytes)
            )
            tel.counter("migrate.rounds").inc(float(total.rounds))
            if self.controller is not None:
                self.controller.observe_migration_measurement(
                    total.payload_bytes, measured_s, modeled_s=modeled_s,
                    step=self.step_count,
                )
        tel.counter("migrate.applies").inc()
        record["sim_time"] = float(self.sim_time)
        tel.record_migration(record)
        tel.emit_span(
            "migrate", self.sim_time, charge,
            moves=record["moves"], via=record["via"],
        )
        return charge

    # ------------------------------------------------------------------
    def step(self) -> dict[str, Any]:
        """One engine iteration: ingest arrivals → admit → prefill-chunk →
        decode → sample → bookkeeping (continuous batching). The step and
        each of its phases run inside a wall-clock program span
        (``engine.*``; ``telemetry/README.md``)."""
        with self.telemetry.step_span(
            "engine.step", self.step_count, active=self.scheduler.num_active
        ):
            return self._step()

    def _step(self) -> dict[str, Any]:
        tel = self.telemetry
        step = self.step_count
        with tel.span("engine.admit", step=step):
            self._ingest_arrivals()
            t0 = self.sim_time
            can_admit = self._kv_admit if self.kv_pool is not None else None
            for slot, req in self.scheduler.admit(can_admit=can_admit):
                req.start_step = self.step_count

        if not self.scheduler.active:
            return {"active": 0}

        prefill_charge = self._prefill_phase()
        if self.paged:
            self._ensure_decode_capacity()
        if not self.installed.any():
            # prefill-only step (chunked prefill in flight, or everything
            # was preempted): charge the prefill time, no decode
            if prefill_charge > 0:
                self.sim_step_latencies.append(prefill_charge)
            tel.counter("engine.steps").inc()
            tel.emit_span(
                "step", t0, self.sim_time - t0,
                step=self.step_count, active=self.scheduler.num_active,
            )
            self.step_count += 1
            return {
                "active": self.scheduler.num_active,
                "finished": len(self.finished),
                "sim_latency": prefill_charge,
                "placement_applied": self.placement_applied,
            }

        with tel.span("engine.dispatch", step=step):
            logits, new_caches, moe_aux = self._decode(*self._decode_args())
        self.caches = new_caches
        observe = moe_aux is not None and self.planner is not None
        with tel.span("engine.sync", step=step):
            next_tokens = np.asarray(
                sample(logits, temperature=self.ecfg.temperature,
                       key=jax.random.PRNGKey(self.step_count))
            )
            if observe:
                # GEM Step-1: per-layer expert counts from the staged
                # dispatch plane's MoEAux struct (scan-stacked
                # RouterOutput.expert_counts)
                counts = np.asarray(moe_aux.expert_counts)  # (L, E)
                dropped = int(np.asarray(moe_aux.dropped_tokens).sum())

        sim_latency = prefill_charge + self.ecfg.other_time_per_step
        if observe:
            with tel.span("engine.attribution", step=step):
                counts_virt = np.repeat(counts, self.config.expert_tp, axis=1)
                cost_mx = self._step_cost_matrix(counts_virt)
                shed_latency = None
                if self._shed_enables is not None:
                    # shedding changes what the fleet PAID (adjusted loads
                    # + transfer charge) but not what the control plane
                    # SEES: cost_mx below stays the un-shed matrix for the
                    # controller, attribution, and regret
                    shed_latency = self._shed_step(
                        counts_virt, moe_aux, cost_mx
                    )
                if shed_latency is not None:
                    sim_latency += shed_latency
                elif cost_mx is not None:
                    sim_latency += float(cost_mx.max(axis=1).sum())
                self._observe_attribution(counts_virt)
            with tel.span("engine.regret", step=step):
                self._observe_regret(counts_virt, cost_mx)
            tel.counter("dispatch.dropped_tokens").inc(dropped)
            with tel.span("engine.controller", step=step):
                if self.controller is not None:
                    sim_latency += self._online_step(counts_virt, cost_mx)
                else:
                    for layer in range(self.config.num_layers):
                        self.planner.observe_step(layer, counts_virt[layer])
        tel.emit_span(
            "decode", self.sim_time, sim_latency - prefill_charge,
            step=self.step_count, active=int(self.installed.sum()),
        )
        self.sim_step_latencies.append(sim_latency)
        # _prefill_phase already advanced the clock by its charge (the
        # TTFT stamp needs it); advance by the decode remainder only
        self.sim_time += sim_latency - prefill_charge

        with tel.span("engine.finish", step=step):
            done_slots = []
            decoded = 0
            for slot, req in list(self.scheduler.active.items()):
                if not self.installed[slot]:
                    continue  # still prefilling (chunked): no token yet
                tok = int(next_tokens[slot])
                req.generated.append(tok)
                decoded += 1
                self.last_token[slot] = tok
                self.cur_len[slot] += 1
                if req.done or self.cur_len[slot] >= self.ecfg.max_len - 1:
                    req.finish_step = self.step_count
                    req.finish_time = self.sim_time
                    self.finished.append(req)
                    done_slots.append((slot, req))
            for slot, req in done_slots:
                self.scheduler.release(slot)
                self.cur_len[slot] = 0
                self.installed[slot] = False
                if self.kv_pool is not None:
                    self.kv_pool.release(req.uid)
                    self.block_tables[slot, :] = 0

        if decoded:
            tel.counter("engine.decode_tokens").inc(decoded)
        tel.counter("engine.steps").inc()
        self.step_count += 1
        self._maybe_replan()
        tel.emit_span(
            "step", t0, self.sim_time - t0,
            step=self.step_count - 1, active=self.scheduler.num_active,
        )
        return {
            "active": self.scheduler.num_active,
            "finished": len(self.finished),
            "sim_latency": sim_latency,
            "placement_applied": self.placement_applied,
            "logits": logits,  # (max_batch, V) on device; idle rows too
        }

    def _decode_args(self) -> tuple:
        """Operands of the decode executable for the current slot state."""
        tokens = jnp.asarray(self.last_token[:, None])
        if self.paged:
            # per-row lengths + block tables: ragged slots attend at their
            # true positions through the paged view
            return (
                self.params, self.caches, jnp.asarray(self.cur_len),
                jnp.asarray(self.block_tables), tokens, self.placements,
                self._shed_operand(),
            )
        # single shared cur_len is not enough for ragged slots: use
        # per-slot max — attention masks per-slot validity through
        # cache zero panels (the dense fallback's approximation)
        cur = jnp.asarray(int(self.cur_len.max()))
        return (
            self.params, self.caches, cur, tokens, self.placements,
            self._shed_operand(),
        )

    def decode_hlo(self) -> str:
        """Optimized HLO text of the decode executable compiled for the
        current operands (``tpu_custom_call`` marks a Mosaic kernel)."""
        return self._decode.lower(*self._decode_args()).compile().as_text()

    def run(self, max_steps: int = 10_000) -> list[Request]:
        steps = 0
        while self.scheduler.has_work() and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    # ------------------------------------------------------------------
    def slo_report(self) -> dict[str, float]:
        """Per-request percentile TTFT/TPOT/E2E (serving/slo.py)."""
        return slo_report(self.finished)

    def kv_stats(self) -> dict[str, float]:
        """Paged-pool occupancy/pressure counters (empty when dense)."""
        if self.kv_pool is None:
            return {}
        out = self.kv_pool.stats()
        out["kv_preemptions"] = float(self.preemption_count)
        return out

    @property
    def shed_enables(self) -> np.ndarray | None:
        """Snapshot of the (L,) 0/1 shed-enable flags the *next*
        ``step()`` will dispatch with (one step behind the overflow that
        priced them), or ``None`` when the shed plane is off. Read-only:
        a copy, so callers can log per-step enable histories (fig25)
        without aliasing the engine's decision state."""
        if self._shed_enables is None:
            return None
        return self._shed_enables.copy()

    def latency_report(self) -> dict[str, float]:
        """Step-level latency stats (legacy keys: ``mean_tpot`` etc. are
        *step* latencies) merged with the per-request SLO percentiles
        (``ttft_p99``/``tpot_p99``/``e2e_p99`` — the serving gates) and
        the paged-pool counters."""
        lat = np.asarray(self.sim_step_latencies)
        lat = lat[lat > 0]
        e2e = np.asarray(
            [r.finish_time - r.arrival_time for r in self.finished]
        )
        out = {"steps": float(self.step_count)}
        if len(lat):
            out.update(
                mean_tpot=float(lat.mean()),
                p90_tpot=float(np.quantile(lat, 0.9)),
                p99_tpot=float(np.quantile(lat, 0.99)),
            )
        if len(e2e):
            out["mean_e2e"] = float(e2e.mean())
        out.update(self.slo_report())
        out.update(self.kv_stats())
        if self.controller is not None:
            out.update(
                replans=float(len(self.controller.replans)),
                migration_s=self.controller.total_migration_cost,
                max_moves_per_step=float(self.controller.max_moves_in_step),
            )
        if self._shed_enables is not None:
            out.update(
                shed_tokens=float(self._shed_total),
                shed_overflow_tokens=float(self._shed_overflow_total),
                shed_saved_s=float(self._shed_saved_s),
                shed_transfer_s=float(self._shed_transfer_s),
            )
        measured = [
            r for r in self.migration_records if "measured_s" in r
        ]
        if measured:
            out.update(
                migration_modeled_s=float(
                    sum(r["modeled_s"] for r in measured)
                ),
                migration_measured_s=float(
                    sum(r["measured_s"] for r in measured)
                ),
                migration_payload_bytes=float(
                    sum(r["payload_bytes"] for r in measured)
                ),
                migration_overlap_s=float(
                    sum(r["overlap_s"] for r in measured)
                ),
            )
        if self.attribution is not None and self.attribution.steps > 0:
            summ = self.attribution.summary()
            # report is dict[str, float]: the per-device straggler tally
            # (a list) stays on the accumulator / telemetry snapshot
            out.update(
                (k, v) for k, v in summ.items() if isinstance(v, float)
            )
        if self.regret is not None and self.regret.steps > 0:
            out.update(self.regret.summary())
        return out
