"""GEM's placement search (paper Algorithms 1–4, §3.3.3 + Appendix B).

  * :func:`initial_mapping` — Alg. 2: sort experts by (noised) mean
    utilization, heaviest first, greedily place each on the device that
    minimizes the partial-mapping score, subject to equal per-device capacity.
  * :func:`refine` — Alg. 3: repeatedly apply the single cross-device expert
    swap that most reduces S(M); stop when the relative drop < 0.1%.
  * :func:`gem_place` — Alg. 4: K restarts (20% utilization noise on restarts
    after the first), return the best final mapping.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .score import IncrementalScorer, score
from .types import ExpertTrace, GEMConfig, Placement, VariabilityProfile

__all__ = [
    "SearchResult",
    "initial_mapping",
    "refine",
    "refine_step_batch",
    "gem_place",
]


@dataclasses.dataclass
class SearchResult:
    placement: Placement
    score: float
    restart_scores: list[float]
    swaps_per_restart: list[int]
    initial_score: float  # score of the unrefined best initial mapping

    @property
    def total_swaps(self) -> int:
        return sum(self.swaps_per_restart)


def initial_mapping(
    trace: ExpertTrace,
    profile: VariabilityProfile,
    *,
    noise: float = 0.0,
    rng: np.random.Generator | None = None,
) -> Placement:
    """Alg. 2: greedy heaviest-first construction.

    Experts are sorted by mean utilization (perturbed by ``noise`` for
    restart diversity) and inserted one at a time onto the device yielding the
    lowest partial score. Capacity is E/G per device so the final mapping is
    balanced (equal expert-weight memory per device, §3.3.3).
    """
    util = trace.mean_utilization().astype(np.float64)
    if noise > 0.0:
        if rng is None:
            rng = np.random.default_rng(0)
        util = util * (1.0 + rng.uniform(-noise, noise, size=util.shape))
    order = np.argsort(-util, kind="stable")

    scorer = IncrementalScorer(trace, profile)
    cap = trace.num_experts // profile.num_devices
    for e in order:
        counts = scorer.placed_count()
        cand = scorer.score_with_add(int(e))
        cand[counts >= cap] = np.inf  # full devices are ineligible
        g = int(cand.argmin())
        scorer.add_expert(int(e), g)
    return scorer.placement()


def refine(
    placement: Placement,
    trace: ExpertTrace,
    profile: VariabilityProfile,
    *,
    tol: float = 1e-3,
    max_swaps: int = 200,
) -> tuple[Placement, float, int]:
    """Alg. 3: best-pair-swap hill climbing until relative drop < ``tol``.

    Returns (refined placement, final score, number of swaps applied).
    """
    scorer = IncrementalScorer(trace, profile)
    scorer.load_placement(placement)
    cur = scorer.score()
    swaps = 0
    while swaps < max_swaps:
        e_a, e_b, new = scorer.best_swap()
        if e_a < 0 or new >= cur:
            break  # no swap improves the score
        drop = cur - new
        scorer.apply_swap(e_a, e_b)
        swaps += 1
        prev = cur
        cur = new
        if drop / max(prev, 1e-30) < tol:
            break  # converged (< 0.1% relative improvement)
    return scorer.placement(), cur, swaps


def _curve_table(profile: VariabilityProfile, max_tokens: int) -> np.ndarray:
    """(G, W) device curves at integer token counts ``0 .. W-1``, by the
    same ``np.interp`` the scorer uses, so a lookup is bit-identical.
    ``np.interp`` holds the last sample flat, so the table stops at the
    grid's end and larger counts read its last column."""
    xp = profile.token_counts.astype(np.float64)
    width = int(min(max_tokens, max(int(profile.token_counts[-1]), 0))) + 1
    grid = np.arange(width, dtype=np.float64)
    return np.stack([np.interp(grid, xp, fp) for fp in profile.latencies])


def refine_step_batch(
    device_of: np.ndarray,
    counts: np.ndarray,
    profile: VariabilityProfile,
    *,
    tol: float = 1e-3,
    max_swaps: int = 200,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """:func:`refine` over B independent one-step traces at once.

    ``device_of`` (B, E): each problem's balanced seed placement;
    ``counts`` (B, E): its single step's expert token counts (cast to
    int64, as :class:`ExpertTrace` does). Returns ``(device_of, scores,
    swaps, rounds)``: per problem exactly the placement, score and swap
    count ``refine(seed, one-step trace, profile, tol=tol,
    max_swaps=max_swaps)`` gives, and the number of batched rounds run.

    With one step the score is the straggler's cost, so a swap that
    touches neither device at the (first) maximum leaves it standing and
    cannot improve. Each round therefore scores only the pairs between
    that device's experts and the rest, for every live problem in one
    pass. Among the pairs of least score it picks the first in
    ``triu_indices`` order, as ``best_swap``'s ``argmin`` does, and stops
    a problem by :func:`refine`'s rules; stopped problems leave the batch.
    """
    dev = np.array(device_of, dtype=np.int32)
    cnt = np.asarray(counts).astype(np.int64)
    if dev.ndim != 2 or dev.shape != cnt.shape:
        raise ValueError("device_of and counts must be (problems, experts)")
    B, E = dev.shape
    G = profile.num_devices
    per_device = (dev[:, :, None] == np.arange(G)).sum(axis=1)
    if E % G or (per_device != E // G).any():
        raise ValueError("every seed must give each device E/G experts")
    K = E // G
    rows = np.arange(B)
    tokens = np.zeros((B, G), dtype=np.int64)
    np.add.at(tokens, (rows[:, None], dev), cnt)
    table = _curve_table(profile, int(tokens.sum(axis=1).max(initial=0)))
    top = table.shape[1] - 1
    flat = table.ravel()

    def cost(g, n):
        return flat[g * (top + 1) + np.minimum(n, top)]

    costs = cost(np.arange(G)[None, :], tokens)  # (B, G)
    cur = costs.max(axis=1)
    swaps = np.zeros(B, dtype=np.int64)
    live = np.flatnonzero(np.full(B, max_swaps > 0))
    rounds = 0
    while live.size:
        rounds += 1
        b = live.size
        d, c, tok, cst = dev[live], cnt[live], tokens[live], costs[live]
        r = np.arange(b)
        gs = cst.argmax(axis=1)  # the straggler, first on ties
        # S: the straggler's experts, ascending (b, K)
        S = np.nonzero(d == gs[:, None])[1].reshape(b, K)
        cS = np.take_along_axis(c, S, axis=1)
        # the max over devices other than the straggler and d, per d
        rest = cst.copy()
        rest[r, gs] = -np.inf
        i1 = rest.argmax(axis=1)
        v1 = rest[r, i1]
        rest[r, i1] = -np.inf
        v2 = rest.max(axis=1)
        others = np.where(
            np.arange(G)[None, :] == i1[:, None], v2[:, None], v1[:, None]
        )
        # pair (S[i], f): f's count moves onto the straggler, S[i]'s off it
        delta = c[:, None, :] - cS[:, :, None]  # (b, K, E)
        new_s = cost(gs[:, None, None], tok[r, gs][:, None, None] + delta)
        new_f = cost(
            d[:, None, :],
            np.take_along_axis(tok, d, axis=1)[:, None, :] - delta,
        )
        # a partner on the straggler is no pair: its max reads +inf
        others_f = np.take_along_axis(others, d, axis=1)
        others_f[d == gs[:, None]] = np.inf
        pair = np.maximum(others_f[:, None, :], np.maximum(new_s, new_f))
        pair = pair.reshape(b, -1)
        new = pair.min(axis=1)
        # first pair in triu order among the least: smallest (lo, hi)
        at, flat_at = np.nonzero(pair == new[:, None])
        e_s, e_f = S[at, flat_at // E], flat_at % E
        best = np.full(b, E * E)
        np.minimum.at(
            best, at, np.minimum(e_s, e_f) * E + np.maximum(e_s, e_f)
        )
        prev = cur[live]
        improves = new < prev
        go = live[improves]
        lo, hi = best[improves] // E, best[improves] % E
        g_lo, g_hi = dev[go, lo], dev[go, hi]
        moved = cnt[go, hi] - cnt[go, lo]
        tokens[go, g_lo] += moved
        tokens[go, g_hi] -= moved
        dev[go, lo], dev[go, hi] = g_hi, g_lo
        costs[go, g_lo] = cost(g_lo, tokens[go, g_lo])
        costs[go, g_hi] = cost(g_hi, tokens[go, g_hi])
        swaps[go] += 1
        cur[go] = new[improves]
        drop = prev[improves] - new[improves]
        keep = (drop / np.maximum(prev[improves], 1e-30) >= tol) & (
            swaps[go] < max_swaps
        )
        live = go[keep]
    return dev, cur, swaps, rounds


def gem_place(
    trace: ExpertTrace,
    profile: VariabilityProfile,
    config: GEMConfig = GEMConfig(),
) -> SearchResult:
    """Alg. 4: K noisy restarts of (Alg. 2 → Alg. 3); return the best mapping."""
    rng = np.random.default_rng(config.seed)
    best: Placement | None = None
    best_score = np.inf
    restart_scores: list[float] = []
    swaps_per_restart: list[int] = []
    best_initial = np.inf
    for i in range(config.num_restarts):
        noise = 0.0 if i == 0 else config.restart_noise
        m0 = initial_mapping(trace, profile, noise=noise, rng=rng)
        s0 = score(trace, profile, m0)
        best_initial = min(best_initial, s0)
        m, s, n_swaps = refine(
            m0,
            trace,
            profile,
            tol=config.convergence_tol,
            max_swaps=config.max_swaps,
        )
        restart_scores.append(s)
        swaps_per_restart.append(n_swaps)
        if s < best_score:
            best_score = s
            best = m
    assert best is not None
    return SearchResult(
        placement=best,
        score=best_score,
        restart_scores=restart_scores,
        swaps_per_restart=swaps_per_restart,
        initial_score=best_initial,
    )
