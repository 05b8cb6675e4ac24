"""Serving plane: arrival generators, paged KV pool, admission, preemption,
and trace-replay parity of the continuous-batching engine."""
import dataclasses

import numpy as np
import pytest

from repro.serving import (
    ArrivalConfig,
    DEFAULT_TASKS,
    PagedKVPool,
    Request,
    Scheduler,
    batch_arrivals,
    blocks_for_tokens,
    generate_arrivals,
    kv_pool_bytes,
    replica_slots_for_headroom,
)

VOCAB = 1024


# ---------------------------------------------------------------------------
# arrivals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("process", ["poisson", "diurnal", "burst"])
def test_arrivals_deterministic_in_seed(process):
    cfg = ArrivalConfig(rate=20.0, num_requests=24, process=process)
    a = generate_arrivals(cfg, VOCAB, seed=3)
    b = generate_arrivals(cfg, VOCAB, seed=3)
    c = generate_arrivals(cfg, VOCAB, seed=4)
    assert len(a) == len(b) == cfg.num_requests
    for ra, rb in zip(a, b):
        assert ra.arrival_time == rb.arrival_time
        assert ra.max_new_tokens == rb.max_new_tokens
        assert ra.task == rb.task
        np.testing.assert_array_equal(ra.prompt, rb.prompt)
    assert any(
        ra.arrival_time != rc.arrival_time for ra, rc in zip(a, c)
    )
    times = np.asarray([r.arrival_time for r in a])
    assert (times > 0).all() and (np.diff(times) >= 0).all()


def test_arrival_mix_shift_switches_tasks():
    chat, summ = DEFAULT_TASKS
    cfg = ArrivalConfig(rate=50.0, num_requests=40)
    specs = generate_arrivals(
        cfg, VOCAB, seed=0,
        mix=[(chat, 1.0)], mix_shift=(0.4, [(summ, 1.0)]),
    )
    before = [s for s in specs if s.arrival_time < 0.4]
    after = [s for s in specs if s.arrival_time >= 0.4]
    assert before and after
    assert all(s.task == "chat" for s in before)
    assert all(s.task == "summarize" for s in after)
    # disjoint vocab bands: the shift moves the prompts' token range
    assert max(int(s.prompt.max()) for s in before) < VOCAB // 2
    assert min(int(s.prompt.min()) for s in after) >= VOCAB // 2


def test_batch_arrivals_is_degenerate_at_t0():
    prompts = [np.arange(4), np.arange(6)]
    specs = batch_arrivals(prompts, 8)
    assert [s.arrival_time for s in specs] == [0.0, 0.0]
    assert [s.max_new_tokens for s in specs] == [8, 8]


# ---------------------------------------------------------------------------
# paged KV pool
# ---------------------------------------------------------------------------

def test_pool_conservation_and_exclusive_ownership():
    pool = PagedKVPool(9, 4)
    assert pool.usable_blocks == 8
    assert pool.allocate(1, 10)  # 3 blocks
    assert pool.allocate(2, 4)  # 1 block
    pool.check_invariants()
    # deterministic lowest-first layout; block 0 never handed out
    assert pool.block_table(1) == [1, 2, 3]
    assert pool.block_table(2) == [4]
    assert pool.used_blocks == 4 and pool.free_blocks == 4
    pool.release(1)
    pool.check_invariants()
    assert pool.used_blocks == 1
    # grow-to-cover is idempotent at the same length
    assert pool.allocate(2, 4)
    assert pool.block_table(2) == [4]
    pool.release(2)
    pool.check_invariants()
    assert pool.used_blocks == 0


def test_pool_double_release_raises():
    pool = PagedKVPool(4, 2)
    assert pool.allocate(7, 2)
    pool.release(7)
    with pytest.raises(KeyError):
        pool.release(7)
    pool.check_invariants()


def test_pool_allocation_is_all_or_nothing():
    pool = PagedKVPool(5, 2)  # 4 usable
    assert pool.allocate(1, 6)  # 3 blocks
    free_before = pool.free_blocks
    assert not pool.allocate(2, 4)  # needs 2, only 1 free
    assert pool.free_blocks == free_before  # nothing leaked
    assert pool.alloc_failures == 1
    assert not pool.holds(2) or pool.block_table(2) == []
    pool.check_invariants()


def test_pool_watermark_reserve():
    pool = PagedKVPool(6, 2, watermark_blocks=2)  # 5 usable
    assert pool.can_allocate(6)  # 3 <= 5 - 2
    assert not pool.can_allocate(8)  # 4 > 5 - 2
    assert pool.can_allocate(8, reserve=0)  # explicit override


def test_pool_slot_tables_null_padding():
    pool = PagedKVPool(8, 4)
    pool.allocate(5, 9)  # 3 blocks
    view = pool.slot_tables([None, 5], n_max=5)
    np.testing.assert_array_equal(view[0], np.zeros(5, np.int32))
    np.testing.assert_array_equal(view[1], [1, 2, 3, 0, 0])


def test_blocks_for_tokens():
    assert blocks_for_tokens(0, 16) == 0
    assert blocks_for_tokens(1, 16) == 1
    assert blocks_for_tokens(16, 16) == 1
    assert blocks_for_tokens(17, 16) == 2


# ---------------------------------------------------------------------------
# scheduler admission
# ---------------------------------------------------------------------------

def _req(uid, plen):
    return Request(uid, np.zeros(plen, np.int32), max_new_tokens=4)


def test_admit_skips_over_budget_head_without_starving_it():
    """Head-of-line regression: an over-budget request at the head must not
    block smaller queued requests from free slots — but it keeps its queue
    position and first claim on the next step's fresh budget."""
    sched = Scheduler(4, prefill_token_budget=100)
    big = _req(1, 90)
    small_a, small_b = _req(2, 30), _req(3, 30)
    for r in (big, small_a, small_b):
        sched.submit(r)
    admitted = sched.admit()
    uids = [r.uid for _, r in admitted]
    # fresh budget: head admits first (90), one small one rides along? no —
    # 90 + 30 > 100, so the smalls are skipped THIS step but the head lands
    assert uids[0] == 1
    # next wave of budget admits the smalls in FCFS order
    uids2 = [r.uid for _, r in sched.admit()]
    assert uids2 == [2, 3]


def test_admit_head_over_budget_smalls_proceed():
    """The actual HOL case: budget too small for the head even alone is
    impossible (progress guarantee admits it), so pin the head with a KV-free
    scheduler whose budget fits the smalls after the head consumed it."""
    sched = Scheduler(2, prefill_token_budget=100)
    sched.submit(_req(1, 80))
    sched.submit(_req(2, 80))
    sched.submit(_req(3, 10))
    uids = [r.uid for _, r in sched.admit()]
    # head (80) admits; second 80 over the remaining budget is skipped in
    # place; the 10-token request behind it takes the second slot
    assert uids == [1, 3]
    assert sched.queue[0].uid == 2  # skipped request kept its position
    sched.release(0)
    sched.release(1)
    assert [r.uid for _, r in sched.admit()] == [2]


def test_admit_progress_guarantee_for_giant_head():
    sched = Scheduler(2, prefill_token_budget=16)
    sched.submit(_req(1, 64))  # over the whole budget
    uids = [r.uid for _, r in sched.admit()]
    assert uids == [1]  # admitted anyway: head + empty admission set


def test_admit_kv_blocked_head_ends_scan():
    """KV blocks free only on completion — skipping a memory-blocked head
    would let later arrivals starve it, so the scan stops."""
    sched = Scheduler(4, prefill_token_budget=1000)
    sched.submit(_req(1, 10))
    sched.submit(_req(2, 10))
    admitted = sched.admit(can_admit=lambda r: r.uid != 1)
    assert admitted == []
    assert [r.uid for r in sched.queue] == [1, 2]


def test_admit_lookahead_bounds_scan():
    sched = Scheduler(4, prefill_token_budget=50, admit_lookahead=2)
    sched.submit(_req(1, 40))
    sched.submit(_req(2, 40))  # skipped (budget)
    sched.submit(_req(3, 5))  # within budget but beyond the lookahead
    uids = [r.uid for _, r in sched.admit()]
    assert uids == [1]


def test_requeue_front_restores_service_order():
    sched = Scheduler(1, prefill_token_budget=100)
    first, second = _req(1, 8), _req(2, 8)
    sched.submit(first)
    sched.submit(second)
    [(slot, r)] = sched.admit()
    assert r.uid == 1
    sched.release(slot)
    r.prefill_progress = 8
    sched.requeue_front(r)
    assert r.slot == -1 and r.prefill_progress == 0
    assert [q.uid for q in sched.queue] == [1, 2]


# ---------------------------------------------------------------------------
# shared HBM budget
# ---------------------------------------------------------------------------

def test_replica_slots_for_headroom_monotone():
    kw = dict(d_model=64, expert_d_ff=128, num_layers=4, bytes_per_param=4)
    slot = 3 * 64 * 128 * 4 * 4
    assert replica_slots_for_headroom(-1.0, **kw) == 0
    assert replica_slots_for_headroom(0.0, **kw) == 0
    assert replica_slots_for_headroom(slot - 1, **kw) == 0
    assert replica_slots_for_headroom(slot, **kw) == 1
    assert replica_slots_for_headroom(3.5 * slot, **kw) == 3
    prev = 0
    for h in np.linspace(0, 8 * slot, 17):
        cur = replica_slots_for_headroom(float(h), **kw)
        assert cur >= prev
        prev = cur


def test_kv_pool_bytes_formula():
    # 2 (K+V) · L · N · bs · KV · hd · bytes
    assert kv_pool_bytes(10, 16, 4, 8, 64, 2) == 2 * 4 * 10 * 16 * 8 * 64 * 2


# ---------------------------------------------------------------------------
# engine integration (real JAX data plane)
# ---------------------------------------------------------------------------

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_smoke_config  # noqa: E402
from repro.core import (  # noqa: E402
    DeviceFleet,
    GEMConfig,
    profile_fleet,
    setup_speeds,
    simulator_measure_fn,
)
from repro.models import init_params  # noqa: E402
from repro.serving import EngineConfig, PagedKVConfig, ServingEngine  # noqa: E402
from repro.sharding import host_policy  # noqa: E402


def _engine(**overrides):
    # sliding_window=0: the paged-KV plane only covers full attention (the
    # smoke mixtral's SWA would force the dense fallback via kv_mode=auto)
    cfg = dataclasses.replace(
        get_smoke_config("mixtral-8x7b"), decode_capacity_factor=4.0,
        sliding_window=0,
    )
    policy = host_policy()
    params, _ = init_params(cfg, jax.random.PRNGKey(0), policy, jnp.float32)
    fleet = DeviceFleet.from_speeds(
        setup_speeds("high", 4), tile=8, tile_time=40e-6
    )
    profile = profile_fleet(
        simulator_measure_fn(fleet), 4, max_tokens=512, tile=8, repeats=3
    ).profile
    base = dict(
        max_batch=4, max_len=64,
        gem=GEMConfig(trace_length=8, num_restarts=2),
        replan_after=8, other_time_per_step=1e-4,
    )
    ecfg = EngineConfig(**{**base, **overrides})
    return ServingEngine(params, cfg, policy, ecfg, profile=profile,
                         num_devices=4), cfg


def _prompts(cfg, n, plen=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=plen) for _ in range(n)]


def test_paged_and_dense_engines_generate_identical_tokens():
    eng_p, cfg = _engine(kv_mode="paged")
    eng_d, _ = _engine(kv_mode="dense")
    assert eng_p.paged and not eng_d.paged
    prompts = _prompts(cfg, 4)
    for eng in (eng_p, eng_d):
        for p in prompts:
            eng.submit(p, max_new_tokens=12)
    done_p = {r.uid: r for r in eng_p.run(max_steps=200)}
    done_d = {r.uid: r for r in eng_d.run(max_steps=200)}
    assert len(done_p) == len(done_d) == 4
    for uid, rp in done_p.items():
        assert rp.generated == done_d[uid].generated


def test_donated_paged_engine_generates_identical_tokens(monkeypatch):
    """On the chip the paged engine donates its KV pools to the decode and
    to the install. Forced on here: a small pool that preempts and
    reinstalls between decode steps gives the tokens of an undonated
    engine, and each step really gives its input pools away."""
    import repro.serving.engine as engine_mod

    monkeypatch.setattr(engine_mod, "_donate_caches", lambda: True)
    kw = dict(max_batch=2, kv=PagedKVConfig(block_size=4, num_blocks=8))
    donated, cfg = _engine(**kw)
    monkeypatch.undo()
    plain, _ = _engine(**kw)
    prompts = _prompts(cfg, 2, plen=8, seed=3)
    for eng in (donated, plain):
        for p in prompts:
            eng.submit(p, max_new_tokens=12)
    given = donated.caches["attn"]["k"]
    donated.step()
    assert given.is_deleted()
    done_d = {r.uid: r for r in donated.run(max_steps=300)}
    done_p = {r.uid: r for r in plain.run(max_steps=300)}
    assert len(done_d) == 2 and donated.preemption_count > 0
    for uid, r in done_d.items():
        assert r.generated == done_p[uid].generated


def test_serve_batch_arrivals_matches_submit_run_bit_exact():
    """Trace-replay parity: the all-at-t=0 arrival stream must reproduce
    submit()+run() tokens bit-for-bit."""
    eng_a, cfg = _engine()
    eng_b, _ = _engine()
    prompts = _prompts(cfg, 6, seed=2)
    for p in prompts:
        eng_a.submit(p, max_new_tokens=8)
    done_a = eng_a.run(max_steps=300)
    done_b = eng_b.serve(batch_arrivals(prompts, 8), max_steps=300)
    assert len(done_a) == len(done_b) == 6
    for ra, rb in zip(done_a, done_b):
        assert ra.uid == rb.uid
        assert ra.generated == rb.generated


def test_serve_poisson_stream_completes_with_slo_metrics():
    eng, cfg = _engine(prefill_time_per_token=1e-5)
    specs = generate_arrivals(
        ArrivalConfig(rate=200.0, num_requests=10), cfg.vocab_size, seed=1
    )
    done = eng.serve(specs, max_steps=500)
    assert len(done) == 10
    for r in done:
        assert r.first_token_time >= r.arrival_time
        assert r.finish_time > r.first_token_time
    rep = eng.latency_report()
    for key in ("ttft_p50", "ttft_p99", "tpot_p99", "e2e_p99"):
        assert key in rep and rep[key] >= 0
    assert rep["slo_requests"] == 10
    assert rep["ttft_p50"] <= rep["e2e_p50"]


def test_small_pool_preempts_and_still_finishes_identically():
    """Alloc-failure → preemption round-trip: a pool too small for both
    requests' full lengths must preempt (youngest arrival), recompute, and
    still produce exactly the tokens of an unconstrained run."""
    big, cfg = _engine(max_batch=2)
    small, _ = _engine(
        max_batch=2,
        kv=PagedKVConfig(block_size=4, num_blocks=8),  # 7 usable
    )
    prompts = _prompts(cfg, 2, plen=8, seed=3)
    for eng in (big, small):
        for p in prompts:
            eng.submit(p, max_new_tokens=12)  # 20 tokens = 5 blocks each
    done_big = {r.uid: r for r in big.run(max_steps=300)}
    done_small = {r.uid: r for r in small.run(max_steps=300)}
    assert len(done_small) == 2
    assert small.preemption_count > 0
    for uid, r in done_small.items():
        assert r.generated == done_big[uid].generated
    # every block returned; invariants hold after the round-trip
    small.kv_pool.check_invariants()
    assert small.kv_pool.used_blocks == 0
    assert small.kv_pool.stats()["kv_alloc_failures"] > 0


def test_admission_blocks_until_pool_frees():
    """KV-budget exhaustion at admission: the second request waits in the
    queue (not preempted — never admitted) until the first releases."""
    eng, cfg = _engine(
        max_batch=2,
        kv=PagedKVConfig(block_size=4, num_blocks=7),  # 6 usable
    )
    p = _prompts(cfg, 2, plen=16, seed=4)  # 4 blocks each at admission
    for x in p:
        eng.submit(x, max_new_tokens=4)  # 20 tokens = 5 blocks total
    done = eng.run(max_steps=200)
    assert len(done) == 2
    assert eng.preemption_count == 0  # waited at admission, never evicted
    # serialized: the second only started after the first finished
    starts = {r.uid: r.start_step for r in done}
    finishes = {r.uid: r.finish_step for r in done}
    assert starts[2] > finishes[1]
    eng.kv_pool.check_invariants()


def test_unservable_request_rejected_at_submit():
    eng, cfg = _engine(kv=PagedKVConfig(block_size=4, num_blocks=4))
    with pytest.raises(ValueError, match="could never be served"):
        eng.submit(np.zeros(16, np.int32), max_new_tokens=32)


def test_auto_slots_derived_from_kv_headroom():
    cfg = dataclasses.replace(get_smoke_config("mixtral-8x7b"),
                              sliding_window=0)
    dtype_bytes = 4
    pool_blocks = 1 + 4 * (-(-64 // 16))  # engine's degenerate sizing
    pool = kv_pool_bytes(
        pool_blocks, 16, cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
        dtype_bytes,
    )
    Fv = cfg.expert_d_ff // cfg.expert_tp
    slot = 3 * cfg.d_model * Fv * cfg.num_layers * dtype_bytes
    from repro.replication import ReplicationConfig

    eng, _ = _engine(
        replication=ReplicationConfig(auto_slots=True),
        hbm_budget_bytes=float(pool + 2 * slot + 1),
    )
    assert eng.ecfg.replication.replica_slots == 2
    assert eng.current_rplacements is not None
    # no budget for replicas: engine falls back to the permutation plane
    eng0, _ = _engine(
        replication=ReplicationConfig(auto_slots=True),
        hbm_budget_bytes=float(pool + slot - 1),
    )
    assert eng0.ecfg.replication.replica_slots == 0
    assert eng0.current_rplacements is None
    with pytest.raises(ValueError, match="auto_slots"):
        _engine(replication=ReplicationConfig(auto_slots=True))
