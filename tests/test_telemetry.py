"""Telemetry plane: registry determinism, span tracing, exports, straggler
attribution, and the engine integration (bit-parity + read-throughs)."""
import dataclasses
import json
import types

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (
    DeviceFleet,
    GEMConfig,
    VariabilityProfile,
    profile_fleet,
    setup_speeds,
    simulator_measure_fn,
)
from repro.models import init_params
from repro.online import DriftConfig, LoadDriftDetector, VariabilityDriftDetector
from repro.serving import EngineConfig, PagedKVPool, Request, Scheduler, ServingEngine
from repro.serving.slo import slo_report
from repro.sharding import host_policy
from repro.telemetry import (
    NOISE_FLOOR,
    AttributionAccumulator,
    Registry,
    RegretTracker,
    Telemetry,
    attribute_step,
    read_jsonl,
    to_chrome_trace,
    validate_audit_event,
    write_chrome_trace,
    write_jsonl,
)


# ---------------------------------------------------------------------------
# registry instruments
# ---------------------------------------------------------------------------

def test_counter_monotonic_and_rejects_negative():
    reg = Registry()
    c = reg.counter("engine.steps")
    c.inc()
    c.inc(2.5)
    assert reg.counter("engine.steps") is c  # create-on-first-use
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1.0)


def test_gauge_watermark():
    g = Registry().gauge("kv.used_blocks")
    assert not g.observed
    g.set(3)
    g.set(7)
    g.set(2)
    assert g.value == 2.0 and g.max_value == 7.0 and g.observed


def test_histogram_fixed_buckets_and_redeclaration():
    reg = Registry()
    h = reg.histogram("attr.step_slack_s", (0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 100.0):
        h.observe(v)
    assert h.counts == [1, 2, 0, 1]  # last bucket = overflow
    assert h.total == 4 and h.mean == pytest.approx(101.05 / 4)
    # same boundaries: fine; different: error (deterministic buckets)
    assert reg.histogram("attr.step_slack_s", (0.1, 1.0, 10.0)) is h
    with pytest.raises(ValueError):
        reg.histogram("attr.step_slack_s", (0.2, 1.0))
    with pytest.raises(KeyError):
        reg.histogram("undeclared")
    with pytest.raises(ValueError):
        Registry().histogram("bad", (1.0, 1.0))  # not strictly increasing


def test_snapshot_is_deterministic():
    def build():
        reg = Registry()
        reg.counter("b").inc(2)
        reg.counter("a").inc(1)
        reg.gauge("g").set(4)
        reg.histogram("h", (1.0, 2.0)).observe(1.5)
        return reg.snapshot()

    a, b = build(), build()
    assert a == b
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert list(a["counters"]) == ["a", "b"]  # sorted keys


# ---------------------------------------------------------------------------
# spans + events
# ---------------------------------------------------------------------------

def test_span_records_simulated_clock():
    t = {"now": 1.0}
    tel = Telemetry(clock=lambda: t["now"])
    tel.emit_span("step", 1.0, 0.5, track="engine", step=0)
    # a wall-clock program span is the profiler's, never an event
    with tel.span("engine.step", step=0):
        t["now"] = 1.5
    tel.emit_span("decode", 1.5, 0.25, track="engine")
    tel.instant("preempt", request=7)
    kinds = [(e["kind"], e["name"]) for e in tel.events]
    assert kinds == [("span", "step"), ("span", "decode"),
                     ("instant", "preempt")]
    assert tel.events[0]["ts"] == 1.0 and tel.events[0]["dur"] == 0.5
    assert tel.events[2]["ts"] == 1.5
    assert tel.events[2]["args"] == {"request": 7}


def test_disabled_hub_records_no_events_but_counts():
    tel = Telemetry(enabled=False)
    with tel.span("step"):
        pass
    tel.instant("preempt")
    tel.counter("engine.steps").inc()
    tel.record_migration({"step": 3, "moves": 2})
    assert tel.events == []  # event surface fully gated
    assert tel.counter("engine.steps").value == 1.0  # registry still live
    assert tel.migration_records == [{"step": 3, "moves": 2}]


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _populated_hub():
    t = {"now": 0.0}
    tel = Telemetry(clock=lambda: t["now"])
    tel.counter("engine.steps").inc(2)
    tel.gauge("kv.used_blocks").set(5)
    tel.histogram("attr.step_slack_s", (1e-3, 1e-2)).observe(2e-3)
    tel.emit_span("step", 0.0, 0.5, step=0)
    tel.emit_span("expert_compute", 0.1, 0.2, track="device1", straggler=True)
    tel.emit_span("expert_compute", 0.1, 0.3, track="device0", straggler=False)
    tel.instant("drift.load", level=1.2)
    return tel


def test_jsonl_round_trip(tmp_path):
    tel = _populated_hub()
    path = str(tmp_path / "events.jsonl")
    n = write_jsonl(tel, path, figure="test", seed=0)
    assert n == 2 + len(tel.events)  # header + events + trailer
    doc = read_jsonl(path)
    assert doc["meta"] == {"figure": "test", "seed": 0}
    assert doc["events"] == tel.events
    assert doc["metrics"] == tel.registry.snapshot()


def test_read_jsonl_rejects_malformed(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"kind": "span", "name": "x", "ts": 0, "dur": 1}\n')
    with pytest.raises(ValueError, match="header"):
        read_jsonl(str(p))
    p.write_text('{"kind": "header", "schema": "other/v9"}\n'
                 '{"kind": "metrics", "snapshot": '
                 '{"counters": {}, "gauges": {}, "histograms": {}}}\n')
    with pytest.raises(ValueError, match="schema"):
        read_jsonl(str(p))
    p.write_text('{"kind": "header", "schema": "repro.telemetry/v1"}\n'
                 '{"kind": "bogus", "name": "x", "ts": 0}\n'
                 '{"kind": "metrics", "snapshot": '
                 '{"counters": {}, "gauges": {}, "histograms": {}}}\n')
    with pytest.raises(ValueError, match="bad kind"):
        read_jsonl(str(p))


def test_chrome_trace_structure(tmp_path):
    tel = _populated_hub()
    doc = to_chrome_trace(tel, figure="test")
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    # engine first, then devices in numeric order
    assert [m["args"]["name"] for m in meta] == [
        "engine", "device0", "device1"
    ]
    tid = {m["args"]["name"]: m["tid"] for m in meta}
    spans = [e for e in events if e["ph"] == "X"]
    assert any(
        e["name"] == "expert_compute" and e["tid"] == tid["device1"]
        and e["ts"] == pytest.approx(0.1e6)
        and e["dur"] == pytest.approx(0.2e6)
        for e in spans
    )  # seconds → microseconds
    instants = [e for e in events if e["ph"] == "i"]
    assert instants and all(e["s"] == "t" for e in instants)
    path = str(tmp_path / "trace.json")
    assert write_chrome_trace(tel, path) == len(events)
    from benchmarks.telemetry_report import parse_chrome_trace
    assert parse_chrome_trace(path)["otherData"]["schema"] == \
        "repro.telemetry/v1"


# ---------------------------------------------------------------------------
# straggler attribution
# ---------------------------------------------------------------------------

def _hetero_profile(speeds):
    grid = np.arange(0, 65, 4, dtype=np.int64)
    lat = np.stack([grid * 1e-5 / s for s in speeds])
    return VariabilityProfile(grid, lat, tile_size=1)


def test_attribution_components_sum_to_total():
    prof = _hetero_profile([1.0, 0.8, 1.3, 0.6])
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 60, size=(6, 4))
    att = attribute_step(tokens, prof)
    np.testing.assert_allclose(
        att.slack_total, att.slack_load + att.slack_var, atol=1e-15
    )
    assert (att.slack_total >= 0).all() and (att.slack_load >= 0).all()
    # the straggler is the argmax of actual per-device cost
    actual = prof.cost_all(tokens.astype(float))
    np.testing.assert_array_equal(att.straggler, actual.argmax(axis=1))


def test_attribution_uniform_fleet_is_all_load():
    prof = _hetero_profile([1.0, 1.0, 1.0, 1.0])
    tokens = np.array([[40, 8, 8, 8], [4, 4, 4, 52]])
    att = attribute_step(tokens, prof)
    np.testing.assert_allclose(att.slack_var, 0.0, atol=1e-15)
    assert att.total > 0 and att.load == pytest.approx(att.total)


def test_attribution_uniform_load_is_all_variability():
    prof = _hetero_profile([1.0, 0.5, 2.0, 1.0])
    tokens = np.full((3, 4), 16)
    att = attribute_step(tokens, prof)
    np.testing.assert_allclose(att.slack_load, 0.0, atol=1e-15)
    assert att.total > 0 and att.var == pytest.approx(att.total)


def test_attribution_accumulator_summary():
    prof = _hetero_profile([1.0, 0.8, 1.3, 0.6])
    acc = AttributionAccumulator(4)
    L = 5
    for s in range(3):
        tokens = np.roll(np.array([[48, 4, 4, 4]] * L), s, axis=1)
        acc.observe(attribute_step(tokens, prof))
    summ = acc.summary()
    assert summ["attr_steps"] == 3.0
    assert summ["attr_slack_total_s"] == pytest.approx(
        summ["attr_slack_load_s"] + summ["attr_slack_var_s"]
    )
    if summ["attr_slack_total_s"] > 0:
        assert summ["attr_load_frac"] + summ["attr_var_frac"] == \
            pytest.approx(1.0)
    assert sum(summ["attr_straggler_cells"]) == 3 * L


# ---------------------------------------------------------------------------
# plane counters (host-side, no engine needed)
# ---------------------------------------------------------------------------

def test_scheduler_admission_counters():
    tel = Telemetry()
    sched = Scheduler(2, prefill_token_budget=4, admit_lookahead=4)
    sched.telemetry = tel
    for uid in range(2):
        sched.submit(Request(uid, np.arange(10, dtype=np.int32), 4))
    admitted = sched.admit()
    # head admitted over-budget (progress guarantee), second budget-skipped
    assert len(admitted) == 1
    assert tel.counter("sched.admitted").value == 1.0
    assert tel.counter("sched.budget_skips").value == 1.0


def test_kv_pool_counters_and_gauge():
    tel = Telemetry()
    pool = PagedKVPool(5, 2)  # 4 usable
    pool.telemetry = tel
    assert pool.allocate(1, 6)  # 3 blocks
    assert not pool.allocate(2, 4)  # fails: 2 needed, 1 free
    pool.release(1)
    assert tel.counter("kv.alloc_failures").value == 1.0
    g = tel.gauge("kv.used_blocks")
    assert g.value == 0.0 and g.max_value == 3.0


def test_drift_detectors_emit_fires():
    tel = Telemetry()
    cfg = DriftConfig(min_steps=2, threshold=0.1)
    load = LoadDriftDetector(2, 4, cfg, telemetry=tel)
    load.set_reference(np.full((2, 4), 25.0))
    shifted = np.array([[97, 1, 1, 1], [97, 1, 1, 1]], dtype=float)
    fired = False
    for _ in range(40):
        fired = load.update(shifted) or fired
    assert fired
    assert tel.counter("controller.drift.load_fires").value >= 1.0
    assert tel.gauge("controller.drift.load_level").value > 0.1
    assert any(e["name"] == "drift.load" for e in tel.events)

    var = VariabilityDriftDetector(4, cfg, telemetry=tel)
    slow = np.array([1.0, 1.0, 1.0, 2.5])
    fired = False
    for _ in range(10):
        fired = var.update(slow, np.ones(4)) or fired
    assert fired
    assert tel.counter("controller.drift.var_fires").value >= 1.0
    assert any(e["name"] == "drift.var" for e in tel.events)


def test_dispatch_counts_dropped_tokens():
    import jax
    import jax.numpy as jnp

    from repro.models.dispatch import build_dispatch, route
    from repro.models.moe import identity_placement, init_moe

    cfg = dataclasses.replace(get_smoke_config("mixtral-8x7b"))
    policy = host_policy()
    params, _ = init_moe(jax.random.PRNGKey(0), cfg, num_layers=1,
                         dtype=jnp.float32, policy=policy)
    lp = jax.tree.map(lambda t: t[0], params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    router = route(x.reshape(1, 32, cfg.d_model), lp["router"], cfg, policy,
                   backend="einsum")
    table = identity_placement(cfg, 1)[0]
    # capacity_factor 8: nothing dropped; 0.1: the tiny capacity must drop
    roomy = build_dispatch(router, table, cfg, policy, capacity_factor=8.0)
    tight = build_dispatch(router, table, cfg, policy, capacity_factor=0.1)
    assert int(roomy.dropped_tokens) == 0
    assert int(tight.dropped_tokens) > 0
    # the count and the legacy fraction describe the same drop
    total = 32 * cfg.experts_per_token
    assert float(tight.dropped) == pytest.approx(
        int(tight.dropped_tokens) / total
    )


# ---------------------------------------------------------------------------
# SLO report edge cases
# ---------------------------------------------------------------------------

def _fake_req(arrival, first, finish, n_tokens):
    return types.SimpleNamespace(
        arrival_time=arrival, first_token_time=first, finish_time=finish,
        generated=list(range(n_tokens)),
    )


def test_slo_report_empty():
    rep = slo_report([])
    assert rep == {"slo_requests": 0.0, "slo_excluded": 0.0}


def test_slo_report_single_request():
    # 1 prefill token at t=1, then 4 decode tokens until t=3
    rep = slo_report([_fake_req(0.5, 1.0, 3.0, 5)])
    assert rep["slo_requests"] == 1.0
    assert rep["ttft_p50"] == rep["ttft_p99"] == pytest.approx(0.5)
    assert rep["tpot_mean"] == pytest.approx(2.0 / 4)
    assert rep["e2e_p99"] == pytest.approx(2.5)


def test_slo_report_excludes_never_started():
    rep = slo_report([_fake_req(0.0, -1.0, 2.0, 3),
                      _fake_req(0.0, 1.0, 2.0, 3)])
    assert rep["slo_requests"] == 1.0 and rep["slo_excluded"] == 1.0


def test_slo_report_golden_p99_interpolation():
    # e2e values 1..16 → linear-interpolated p99 = 1 + 15 * 0.99 = 15.85
    reqs = [_fake_req(0.0, 0.5 * v, float(v), 2) for v in range(1, 17)]
    rep = slo_report(reqs)
    assert rep["slo_requests"] == 16.0
    assert rep["e2e_p99"] == pytest.approx(15.85)
    assert rep["e2e_p50"] == pytest.approx(8.5)
    vals = np.arange(1.0, 17.0)
    assert rep["e2e_p99"] == pytest.approx(float(np.quantile(vals, 0.99)))


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_pair():
    """The same stream through telemetry-off and telemetry-on engines."""
    import jax
    import jax.numpy as jnp

    cfg = dataclasses.replace(
        get_smoke_config("mixtral-8x7b"), decode_capacity_factor=4.0
    )
    policy = host_policy()
    params, _ = init_params(cfg, jax.random.PRNGKey(0), policy, jnp.float32)
    fleet = DeviceFleet.from_speeds(
        setup_speeds("high", 4), tile=8, tile_time=40e-6
    )
    profile = profile_fleet(
        simulator_measure_fn(fleet), 4, max_tokens=512, tile=8, repeats=3
    ).profile
    ecfg = EngineConfig(
        max_batch=4, max_len=80,
        gem=GEMConfig(trace_length=8, num_restarts=4),
        replan_after=8, other_time_per_step=1e-4,
        placement_policy="gem",
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=12) for _ in range(4)]
    runs = {}
    for mode, hub in (("off", None), ("on", Telemetry())):
        eng = ServingEngine(params, cfg, policy, ecfg, profile=profile,
                            num_devices=4, telemetry=hub)
        for p in prompts:
            eng.submit(p, max_new_tokens=10)
        done = eng.run(max_steps=300)
        runs[mode] = (eng, done)
    return runs


def test_engine_telemetry_off_is_bit_identical(engine_pair):
    off_eng, off_done = engine_pair["off"]
    on_eng, on_done = engine_pair["on"]
    by_uid = {r.uid: r for r in off_done}
    assert len(on_done) == len(off_done) == 4
    for r in on_done:
        assert r.generated == by_uid[r.uid].generated
    assert off_eng.telemetry.events == []  # default hub is disabled
    assert on_eng.telemetry.events  # live hub recorded the run


def test_engine_registry_read_throughs(engine_pair):
    for mode in ("off", "on"):
        eng, _ = engine_pair[mode]
        tc = eng.jit_trace_counts
        # one trace per shape bucket, never per step — and the property is
        # a read-through of the registry (single source of truth)
        assert tc["decode"] >= 1
        assert tc["decode"] == int(
            eng.telemetry.counter("jit.trace.decode").value
        )
        assert eng.migration_records is eng.telemetry.migration_records
        if eng.placement_applied:
            assert eng.migration_records
            rec = eng.migration_records[0]
            assert {"step", "via", "moves", "modeled_s", "sim_time"} <= set(rec)
            assert eng.telemetry.counter("migrate.applies").value >= 1.0


def test_engine_step_counters_and_attribution(engine_pair):
    eng, _ = engine_pair["on"]
    reg = eng.telemetry.registry
    assert reg.counter("engine.steps").value == eng.step_count
    assert reg.counter("engine.decode_tokens").value == pytest.approx(4 * 10)
    assert reg.counter("engine.prefill_tokens").value == pytest.approx(4 * 12)
    # attribution ran every MoE step and its invariant holds cumulatively
    snap = reg.snapshot()
    total = snap["counters"]["attr.slack_total_s"]
    load = snap["counters"]["attr.slack_load_s"]
    var = snap["gauges"]["attr.slack_var_s"]["value"]
    assert total == pytest.approx(load + var)
    assert eng.attribution.steps > 0
    rep = eng.latency_report()
    assert rep["attr_slack_total_s"] == pytest.approx(total)
    assert all(isinstance(v, float) for v in rep.values())


def test_engine_records_regret_search_rounds(engine_pair):
    eng, _ = engine_pair["on"]
    snap = eng.telemetry.registry.snapshot()
    assert eng.regret.steps > 0
    assert snap["counters"]["regret.search_rounds"] >= 0


# ---------------------------------------------------------------------------
# placement regret (hindsight oracle)
# ---------------------------------------------------------------------------

def _actual_cost(counts, prof, placements):
    """Σ_l max_g C_g(n_g) under the live placements — what the run paid."""
    loads = np.stack([
        np.bincount(p.expert_to_device, weights=c, minlength=4)
        for c, p in zip(counts, placements)
    ])
    return float(prof.cost_all(loads).max(axis=1).sum())


def test_regret_nonnegative_and_components_sum_exactly():
    from repro.core import linear_placement

    prof = _hetero_profile([1.0, 0.7, 1.4, 0.9])
    tr = RegretTracker(8, 4, keep_series=True)
    placements = [linear_placement(8, 4) for _ in range(2)]
    rng = np.random.default_rng(0)
    for s in range(6):
        counts = rng.integers(0, 40, size=(2, 8))
        actual = _actual_cost(counts, prof, placements)
        sr = tr.observe(counts, prof, actual,
                        placements=placements, lagging=s < 2)
        assert sr.regret_s >= -NOISE_FLOOR
        assert sr.oracle_s <= sr.actual_s
        assert sr.lower_bound_s <= sr.oracle_s + NOISE_FLOOR
        assert sr.component == ("migration-lag" if s < 2 else "placement")
    summ = tr.summary()
    assert summ["regret_steps"] == 6.0
    # exact, not approximate: every step lands in exactly one component
    assert summ["regret_placement_s"] + summ["regret_migration_lag_s"] == \
        summ["regret_total_s"]
    assert summ["regret_total_s"] == pytest.approx(
        summ["regret_actual_s"] - summ["regret_oracle_s"]
    )
    assert summ["regret_unrecoverable_s"] >= -NOISE_FLOOR


def test_regret_zero_on_uniform_fleet_balanced_load():
    from repro.core import linear_placement

    prof = _hetero_profile([1.0, 1.0, 1.0, 1.0])
    tr = RegretTracker(8, 4)
    placements = [linear_placement(8, 4)]
    counts = np.full((1, 8), 16)  # 32 tokens/device everywhere
    actual = _actual_cost(counts, prof, placements)
    sr = tr.observe(counts, prof, actual, placements=placements)
    # nothing to recover: actual == oracle == the placement-free floor
    assert sr.regret_s == pytest.approx(0.0, abs=NOISE_FLOOR)
    assert sr.unrecoverable_s == pytest.approx(0.0, abs=NOISE_FLOOR)


def test_regret_oracle_recovers_hot_expert_misplacement():
    from repro.core import linear_placement

    # fast device 0 idle-ish, slow device 3 carries the hot expert: a
    # hindsight re-search must find a strictly better assignment
    prof = _hetero_profile([1.0, 1.0, 1.0, 0.25])
    placements = [linear_placement(8, 4)]  # experts 6,7 → device 3
    counts = np.zeros((1, 8), dtype=np.int64)
    counts[0, 7] = 48  # hot expert pinned to the slow device
    counts[0, 0] = 4
    tr = RegretTracker(8, 4)
    actual = _actual_cost(counts, prof, placements)
    sr = tr.observe(counts, prof, actual, placements=placements)
    assert sr.regret_s > 0.0
    assert sr.oracle_s < sr.actual_s


def test_record_step_metrics_counters_and_instant():
    from repro.telemetry.regret import StepRegret, record_step_metrics

    tel = Telemetry()
    sr = StepRegret(actual_s=3e-3, oracle_s=2e-3, lower_bound_s=1.5e-3,
                    component="migration-lag")
    record_step_metrics(tel, sr, step=7)
    assert tel.counter("regret.total_s").value == pytest.approx(1e-3)
    assert tel.counter("regret.migration_lag_s").value == pytest.approx(1e-3)
    assert tel.counter("regret.placement_s").value == 0.0
    assert tel.registry.histogram("regret.step_s").total == 1
    (ev,) = [e for e in tel.events if e["name"] == "regret"]
    assert ev["args"]["step"] == 7
    assert ev["args"]["component"] == "migration-lag"
    assert ev["args"]["regret_s"] == pytest.approx(1e-3)


# ---------------------------------------------------------------------------
# decision audit + offline replay
# ---------------------------------------------------------------------------

def _audited_controller_run(tel):
    """A tiny online-controller run with a mid-run load shift: warm-up,
    plan, drift fire, deferred replan, budgeted migration — every decision
    path the audit plane logs."""
    from repro.core import GEMConfig, MigrationCostModel
    from repro.core.gem import GEMPlanner
    from repro.online import MigrationConfig, OnlineConfig, OnlineController

    prof = _hetero_profile([1.0, 0.7, 1.4, 0.9])
    planner = GEMPlanner(8, 4, 2, GEMConfig(trace_length=4, num_restarts=2))
    planner.set_profile(prof)
    ctrl = OnlineController(
        planner,
        MigrationCostModel(expert_bytes=1e6, base_overhead=0.0),
        OnlineConfig(
            drift=DriftConfig(min_steps=2, threshold=0.5),
            migration=MigrationConfig(max_moves_per_step=2),
            replan_cooldown=2, payback_horizon=100_000,
        ),
        telemetry=tel,
    )
    rng = np.random.default_rng(0)
    for s in range(24):
        if s < 12:
            counts = rng.integers(8, 16, size=(2, 8))
        else:  # shift: one expert goes hot in every layer
            counts = rng.integers(0, 4, size=(2, 8))
            counts[:, 5] += 90
        observed = None if s % 3 else prof.cost_all(
            np.full((1, 4), 24.0)
        )[0] * (1.0 + 0.01 * s)
        ctrl.observe_step(counts, observed)
    ctrl.observe_migration_measurement(2e6, 1e-4, modeled_s=9e-5, step=20)
    return ctrl


def test_decision_replay_is_byte_exact(tmp_path):
    from benchmarks.decision_replay import replay_log

    tel = Telemetry()
    ctrl = _audited_controller_run(tel)
    assert ctrl.replans, "run never replanned — the test lost its teeth"
    path = str(tmp_path / "audit.jsonl")
    write_jsonl(tel, path, figure="test", seed=0)
    res = replay_log(path)
    assert res["mismatches"] == []
    assert res["controllers"] == 1
    assert res["steps"] == 24
    assert res["measures"] == 1
    assert res["replans_logged"] == len(ctrl.replans)
    assert res["replans_replayed"] == res["replans_logged"]


def test_decision_replay_detects_tampered_decision(tmp_path):
    from benchmarks.decision_replay import replay_log

    tel = Telemetry()
    _audited_controller_run(tel)
    path = str(tmp_path / "tampered.jsonl")
    write_jsonl(tel, path, figure="test", seed=0)
    lines = open(path).read().splitlines()
    for i, line in enumerate(lines):
        row = json.loads(line)
        if row.get("name") == "audit.step":
            row["args"]["decision"]["migration_cost"] += 1.0
            lines[i] = json.dumps(row, sort_keys=True)
            break
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    res = replay_log(path)
    assert any(m["kind"] == "decision" for m in res["mismatches"])


def test_validate_audit_event_contract():
    validate_audit_event(
        "audit.measure",
        {"step": 1, "payload_bytes": 1.0, "measured_s": 1e-4,
         "modeled_s": 1e-4},
    )
    with pytest.raises(ValueError, match="missing args"):
        validate_audit_event("audit.measure", {"step": 1})
    with pytest.raises(ValueError, match="unknown audit event"):
        validate_audit_event("audit.bogus", {})
    with pytest.raises(ValueError, match="no args dict"):
        validate_audit_event("audit.step", None)


# ---------------------------------------------------------------------------
# read_jsonl robustness (crash-consistent tails, bad spans, bad audits)
# ---------------------------------------------------------------------------

def test_read_jsonl_recover_tail_torn_line(tmp_path):
    tel = _populated_hub()
    path = str(tmp_path / "torn.jsonl")
    write_jsonl(tel, path, figure="test")
    whole = open(path).read().splitlines()
    # crash mid-write: trailer gone, final event line torn in half
    torn = "\n".join(whole[:-2] + [whole[-2][: len(whole[-2]) // 2]]) + "\n"
    with open(path, "w") as f:
        f.write(torn)
    with pytest.raises(ValueError):
        read_jsonl(path)
    doc = read_jsonl(path, recover_tail=True)
    assert doc["recovered"] is True
    assert doc["metrics"] is None
    assert doc["events"] == tel.events[:-1]  # torn event dropped
    # a healthy log is not marked recovered
    write_jsonl(tel, path, figure="test")
    assert "recovered" not in read_jsonl(path)
    assert read_jsonl(path, recover_tail=True)["recovered"] is False


def test_read_jsonl_recover_tail_rejects_mid_file_corruption(tmp_path):
    tel = _populated_hub()
    path = str(tmp_path / "mid.jsonl")
    write_jsonl(tel, path, figure="test")
    lines = open(path).read().splitlines()
    lines[2] = lines[2][:10]  # torn *interior* line: not a tail crash
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.raises(ValueError, match="not JSON"):
        read_jsonl(path, recover_tail=True)


def test_read_jsonl_rejects_out_of_order_span(tmp_path):
    p = tmp_path / "span.jsonl"
    trailer = ('{"kind": "metrics", "snapshot": '
               '{"counters": {}, "gauges": {}, "histograms": {}}}')
    p.write_text(
        '{"kind": "header", "schema": "repro.telemetry/v1"}\n'
        '{"kind": "span", "name": "step", "track": "engine", '
        '"ts": 1.0, "dur": -0.5}\n' + trailer + "\n"
    )
    with pytest.raises(ValueError, match="out of order"):
        read_jsonl(str(p))
    p.write_text(
        '{"kind": "header", "schema": "repro.telemetry/v1"}\n'
        '{"kind": "instant", "name": "x", "track": "engine", "ts": NaN}\n'
        + trailer + "\n"
    )
    with pytest.raises(ValueError, match="non-finite ts"):
        read_jsonl(str(p))


def test_read_jsonl_rejects_malformed_audit_record(tmp_path):
    p = tmp_path / "audit.jsonl"
    trailer = ('{"kind": "metrics", "snapshot": '
               '{"counters": {}, "gauges": {}, "histograms": {}}}')
    p.write_text(
        '{"kind": "header", "schema": "repro.telemetry/v1"}\n'
        '{"kind": "instant", "name": "audit.step", "track": "controller", '
        '"ts": 0.0, "args": {"step": 1}}\n' + trailer + "\n"
    )
    with pytest.raises(ValueError, match="missing args"):
        read_jsonl(str(p))


# ---------------------------------------------------------------------------
# admission-time queue-age / TTFT-slack instruments
# ---------------------------------------------------------------------------

def test_scheduler_queue_age_and_ttft_slack():
    t = {"now": 0.0}
    tel = Telemetry(clock=lambda: t["now"])
    sched = Scheduler(1, ttft_slo_s=0.05)
    sched.telemetry = tel
    a = Request(0, np.arange(4, dtype=np.int32), 4)
    b = Request(1, np.arange(4, dtype=np.int32), 4)
    a.arrival_time = b.arrival_time = 0.0
    sched.submit(a)
    sched.submit(b)
    t["now"] = 0.01
    (admitted_a,) = sched.admit()  # one slot: only the head goes
    t["now"] = 0.2
    sched.release(admitted_a[0])
    (admitted_b,) = sched.admit()
    assert admitted_b[1] is b
    age = tel.registry.histogram("sched.queue_age_s")
    slack = tel.registry.histogram("sched.ttft_slack_s")
    assert age.total == 2 and slack.total == 2
    assert age.sum == pytest.approx(0.01 + 0.2)
    # first admission had 0.04s of slack; the second was 0.15s late
    assert slack.sum == pytest.approx(0.04 - 0.15)
    assert tel.counter("sched.slo_at_risk").value == 1.0
    evs = [e for e in tel.events if e["name"] == "sched.admit"]
    assert [e["args"]["uid"] for e in evs] == [0, 1]
    assert evs[1]["args"]["ttft_slack_s"] == pytest.approx(-0.15)
    assert evs[1]["track"] == "sched"


def test_scheduler_queue_age_without_slo_target():
    tel = Telemetry()
    sched = Scheduler(1)  # no TTFT target configured
    sched.telemetry = tel
    sched.submit(Request(0, np.arange(4, dtype=np.int32), 4))
    sched.admit()
    assert tel.registry.histogram("sched.queue_age_s").total == 1
    with pytest.raises(KeyError):  # slack instrument never declared
        tel.registry.histogram("sched.ttft_slack_s")
    assert tel.counter("sched.slo_at_risk").value == 0.0
    (ev,) = [e for e in tel.events if e["name"] == "sched.admit"]
    assert "ttft_slack_s" not in ev["args"]


def test_engine_trace_exports_round_trip(engine_pair, tmp_path):
    eng, _ = engine_pair["on"]
    events_path = str(tmp_path / "events.jsonl")
    trace_path = str(tmp_path / "trace.json")
    write_jsonl(eng.telemetry, events_path, figure="test")
    write_chrome_trace(eng.telemetry, trace_path)
    doc = read_jsonl(events_path)
    names = {e["name"] for e in doc["events"]}
    assert {"step", "prefill", "decode", "expert_compute"} <= names
    tracks = {e["track"] for e in doc["events"]}
    assert {"device0", "device1", "device2", "device3"} <= tracks
    from benchmarks.telemetry_report import (
        attribution_summary,
        parse_chrome_trace,
        straggler_table,
    )
    parse_chrome_trace(trace_path)
    rows = straggler_table(doc)
    assert len(rows) == 4  # one summary row per device
    assert sum(r["straggler_steps"] for r in rows) == eng.attribution.steps
    attr = attribution_summary(doc)  # raises if the invariant broke
    assert attr is not None and attr["slack_total_s"] >= 0.0
