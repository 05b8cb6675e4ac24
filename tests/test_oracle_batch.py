"""The regret oracle's batched one-step search.

``refine_step_batch`` must give, problem by problem, exactly what
``refine`` gives on a one-step trace (placement, score bit for bit, swap
count), and ``RegretTracker.observe`` built on it must reproduce the
per-layer loop it replaced, step by step and in its summary.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.core import (
    DeviceFleet,
    ExpertTrace,
    Placement,
    VariabilityProfile,
    linear_placement,
    profile_fleet,
    refine,
    refine_step_batch,
    setup_speeds,
    simulator_measure_fn,
)
from repro.telemetry import RegretTracker, Telemetry
from repro.telemetry.regret import record_step_metrics


def _fleet_profile(num_devices: int) -> VariabilityProfile:
    """Staircase curves of a high-variability simulated fleet."""
    fleet = DeviceFleet.from_speeds(
        setup_speeds("high", num_devices), tile=8, tile_time=40e-6
    )
    return profile_fleet(
        simulator_measure_fn(fleet), num_devices,
        max_tokens=512, tile=8, repeats=3,
    ).profile


def _non_monotone_profile() -> VariabilityProfile:
    grid = np.array([0, 8, 16, 32, 64, 128, 512])
    lat = np.array([
        [1.0, 3.0, 2.0, 5.0, 4.0, 9.0, 20.0],
        [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0],
        [2.0, 1.0, 4.0, 3.0, 6.0, 5.0, 30.0],
        [0.5, 4.0, 1.0, 6.0, 2.0, 8.0, 3.0],
    ]) * 1e-4
    return VariabilityProfile(grid, lat, tile_size=8)


def _uniform_profile() -> VariabilityProfile:
    grid = np.arange(0, 65, 4, dtype=np.int64)
    return VariabilityProfile(grid, np.tile(grid * 1e-5, (4, 1)), tile_size=1)


def _balanced_seeds(rng, B, E, G):
    return np.stack([
        rng.permutation(np.repeat(np.arange(G), E // G)) for _ in range(B)
    ]).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class Case:
    profile: str  # "fleet2" / "fleet4" / "fleet8" / "non_monotone" / "uniform"
    experts: int
    max_count: int  # counts drawn from [0, max_count)
    tol: float = 1e-3
    max_swaps: int = 200


CASES = {
    "g2": Case("fleet2", 16, 60),
    "g4": Case("fleet4", 32, 60),
    "g8": Case("fleet8", 64, 60),
    # identical curves, equal or tiny counts: device maxima tie
    "tied_maxima": Case("uniform", 16, 3),
    "non_monotone": Case("non_monotone", 16, 30),
    "max_swaps_1": Case("fleet4", 32, 60, max_swaps=1),
    "tol_stops_after_one": Case("fleet4", 32, 60, tol=0.5),
    "exhaustive_tol_0": Case("fleet4", 32, 60, tol=0.0),
}

_PROFILES = {
    "fleet2": lambda: _fleet_profile(2),
    "fleet4": lambda: _fleet_profile(4),
    "fleet8": lambda: _fleet_profile(8),
    "non_monotone": _non_monotone_profile,
    "uniform": _uniform_profile,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_refine_step_batch_equals_refine(name):
    case = CASES[name]
    prof = _PROFILES[case.profile]()
    G, E, B = prof.num_devices, case.experts, 48
    rng = np.random.default_rng(sorted(CASES).index(name))
    seeds = _balanced_seeds(rng, B, E, G)
    counts = rng.integers(0, case.max_count, size=(B, E))
    if name == "tied_maxima":
        # equal totals on every device of the seed: all four curves tie
        counts = np.ones((B, E), dtype=np.int64)
        counts[B // 2:] = rng.integers(0, 3, size=(B - B // 2, E))
    dev, scores, swaps, rounds = refine_step_batch(
        seeds, counts, prof, tol=case.tol, max_swaps=case.max_swaps
    )
    for b in range(B):
        p, s, n = refine(
            Placement(seeds[b], G), ExpertTrace(counts[b][None, :]), prof,
            tol=case.tol, max_swaps=case.max_swaps,
        )
        np.testing.assert_array_equal(dev[b], p.expert_to_device)
        assert scores[b] == s  # bit for bit, not approximately
        assert swaps[b] == n
    assert rounds >= swaps.max()
    if name == "max_swaps_1":
        assert swaps.max() == 1
    if name == "tol_stops_after_one":
        assert swaps.max() == 1 and (swaps == 1).any()
    if name in ("g4", "g8", "exhaustive_tol_0"):
        # problems left the batch in different rounds
        assert len(set(swaps.tolist())) > 2


def test_refine_step_batch_rejects_unbalanced_seeds():
    prof = _fleet_profile(4)
    seeds = np.zeros((1, 8), dtype=np.int32)
    with pytest.raises(ValueError, match="E/G experts"):
        refine_step_batch(seeds, np.ones((1, 8)), prof)


def test_refine_step_batch_max_swaps_0_runs_no_round():
    prof = _fleet_profile(4)
    seeds = np.tile(linear_placement(8, 4).expert_to_device, (3, 1))
    counts = np.arange(24).reshape(3, 8)
    dev, scores, swaps, rounds = refine_step_batch(
        seeds, counts, prof, max_swaps=0
    )
    assert rounds == 0 and not swaps.any()
    np.testing.assert_array_equal(dev, seeds)
    np.testing.assert_array_equal(
        scores, prof.cost_all(
            np.stack([np.bincount(s, weights=c, minlength=4)
                      for s, c in zip(seeds, counts)])
        ).max(axis=1),
    )


# ---------------------------------------------------------------------------
# tracker: the batched search against the per-layer loop it replaced
# ---------------------------------------------------------------------------

class _PerLayerTracker(RegretTracker):
    """The oracle as a per-layer loop of ``refine`` calls (the reference)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._warm_by_layer: dict[int, Placement] = {}

    def _oracle(self, counts, profile, placements):
        return sum(
            self._oracle_layer(
                layer,
                counts[layer],
                profile,
                placements[layer] if placements is not None else None,
            )
            for layer in range(counts.shape[0])
        )

    def _oracle_layer(self, layer, counts, profile, live):
        trace = ExpertTrace(counts[None, :].astype(np.int64))
        seeds: list[Placement] = []
        if live is not None:
            seeds.append(live)
        prev = self._warm_by_layer.get(layer)
        if prev is not None and not any(
            np.array_equal(prev.expert_to_device, s.expert_to_device)
            for s in seeds
        ):
            seeds.append(prev)
        if not seeds:
            seeds.append(linear_placement(self.num_experts, self.num_devices))
        best_p: Placement | None = None
        best_s = np.inf
        for seed in seeds:
            p, s, _ = refine(
                seed, trace, profile, tol=self.tol, max_swaps=self.max_swaps
            )
            if s < best_s:
                best_p, best_s = p, s
        assert best_p is not None
        self._warm_by_layer[layer] = best_p
        return float(best_s)


def _skewed_steps(rng, steps, L, E, tokens):
    """Per-step (L, E) router counts with a topic flip halfway."""
    pop = rng.dirichlet(np.full(E, 0.4), size=L)
    out = []
    for t in range(steps):
        if t == steps // 2:
            pop = rng.dirichlet(np.full(E, 0.4), size=L)
        out.append(np.stack([rng.multinomial(tokens, p) for p in pop]))
    return out


@pytest.mark.parametrize("mode", ["live_placements", "replicated"])
def test_tracker_matches_per_layer_loop(mode):
    L, E, G, steps = 4, 16, 4, 24
    prof = _fleet_profile(G)
    rng = np.random.default_rng(7)
    live = [
        Placement(rng.permutation(np.repeat(np.arange(G), E // G)), G)
        for _ in range(L)
    ]
    new = RegretTracker(E, G, keep_series=True)
    ref = _PerLayerTracker(E, G, keep_series=True)
    for t, counts in enumerate(_skewed_steps(rng, steps, L, E, 96)):
        if mode == "live_placements" and t == steps // 3:
            # one swap lands mid-run on layer 1
            e2d = live[1].expert_to_device.copy()
            a = 0
            b = int(np.flatnonzero(e2d != e2d[a])[0])
            e2d[a], e2d[b] = e2d[b], e2d[a]
            live[1] = Placement(e2d, G)
        placements = live if mode == "live_placements" else None
        actual = float(
            prof.cost_all(
                np.stack([
                    np.bincount(p.expert_to_device, weights=c, minlength=G)
                    for c, p in zip(counts, live)
                ])
            ).max(axis=1).sum()
        )
        kw = dict(placements=placements, lagging=t % 5 == 0)
        got = new.observe(counts, prof, actual, **kw)
        want = ref.observe(counts, prof, actual, **kw)
        assert got == want  # every StepRegret field, bit for bit
        assert new.last_search_rounds >= 1
    assert new.series == ref.series
    assert new.summary() == ref.summary()
    # and the warm state the next step would start from
    for layer in range(L):
        np.testing.assert_array_equal(
            new._warm[layer], ref._warm_by_layer[layer].expert_to_device
        )


def test_search_rounds_counter_recorded():
    L, E, G = 3, 16, 4
    prof = _fleet_profile(G)
    rng = np.random.default_rng(3)
    placements = [linear_placement(E, G) for _ in range(L)]
    tel = Telemetry()
    tr = RegretTracker(E, G)
    total = 0
    for t, counts in enumerate(_skewed_steps(rng, 4, L, E, 64)):
        sr = tr.observe(counts, prof, 1.0, placements=placements)
        record_step_metrics(tel, sr, t, search_rounds=tr.last_search_rounds)
        total += tr.last_search_rounds
    snap = tel.registry.snapshot()
    assert snap["counters"]["regret.search_rounds"] == total >= 0
