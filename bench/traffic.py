"""Seeded request schedules from the traffic files in ``bench/traffic/``.

One general generator reads every mix; a mix is a JSON file of parameters
and holds no code. A schedule is a list of :class:`Arrival` records, due
times in seconds from the start of the measured window, and knows nothing
of the program under test.

Two seeds are at work. The file's ``structure_seed`` fixes the arrival
times and the multiset of request sizes and topics, so every run of a cell
offers the same work. The run's ``--seed`` permutes which request takes
which arrival (within each half of a mix that shifts) and draws every
token id, so runs still differ in order and content.

The Markov-modulated burst process and the vocabulary-band topics are
copied from ``src/repro/serving/arrivals.py`` and
``src/repro/core/workload.py`` so that later changes to the program cannot
change the yardstick.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Arrival:
    due: float  # seconds from the window's start (warm-up: order only)
    prompt: np.ndarray  # (P,) int32
    max_new_tokens: int
    topic: str


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, salt])


def _burst_mask(num_steps: int, active_frac: float, burst_len: int,
                rng: np.random.Generator) -> np.ndarray:
    """Contiguous on/off phases with the requested stationary active
    fraction (a copy of ``core.workload._burst_mask``)."""
    mask = np.zeros(num_steps, dtype=bool)
    t = 0
    on = rng.random() < active_frac
    while t < num_steps:
        dur = max(1, int(rng.geometric(1.0 / burst_len)))
        if on:
            mask[t: t + dur] = True
        t += dur
        on = rng.random() < active_frac
        if mask[min(t, num_steps) - 1]:
            on = rng.random() < active_frac ** 0.5
    return mask


def arrival_times(spec: dict, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Arrival times in ``[0, seconds)``: Poisson, or Markov-modulated
    Poisson whose regimes run at ``rate·mult`` when on and at
    ``rate·off_scale`` when off (a copy of
    ``serving.arrivals._burst_times``). The copied on/off chain stays on
    more than its nominal share, and over a span of a minute its count
    swings widely with the seed, so the burst times are then stretched to
    hold exactly ``rate·seconds`` arrivals: the offered load is the
    cell's rate, and the bursts keep their shape."""
    rate = float(spec["rate_rps"])
    if spec["process"] == "poisson":
        n = int(rate * seconds * 2) + 64
        t = np.cumsum(rng.exponential(1.0 / rate, size=n))
        return t[t < seconds]
    if spec["process"] != "burst":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    frac, mult = spec["burst_active_frac"], spec["burst_multiplier"]
    regime_len = spec["burst_regime_len"]
    off_scale = max((1.0 - frac * mult) / (1.0 - frac), 0.05)
    regime_dt = regime_len / rate
    want = int(round(rate * seconds))
    n_regimes = 2 * int(np.ceil(seconds / regime_dt)) + 2
    mask = _burst_mask(n_regimes, frac, regime_len, rng)
    times = []
    t = 0.0
    for r in range(n_regimes):
        lam = rate * (mult if mask[r] else off_scale)
        end = (r + 1) * regime_dt
        while len(times) < want:
            t += rng.exponential(1.0 / lam)
            if t >= end:
                t = end
                break
            times.append(t)
    if len(times) < want:
        raise ValueError("burst chain too sparse for the window")
    times = np.asarray(times)
    return times * (seconds * want / ((want + 1) * times[-1]))


def _output_lengths(out: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if "fixed" in out:
        return np.full(n, int(out["fixed"]), np.int64)
    raw = rng.lognormal(np.log(out["median"]), out["sigma"], size=n)
    return np.clip(np.round(raw), out["min"], out["max"]).astype(np.int64)


def _prompt_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    buckets = np.asarray(spec["prompt_buckets"], np.int64)
    w = np.asarray(spec.get("bucket_weights", [1.0] * len(buckets)), float)
    return rng.choice(buckets, size=n, p=w / w.sum())


def _topics(mix, n: int, rng: np.random.Generator) -> np.ndarray:
    w = np.asarray(mix, float)
    return rng.choice(len(w), size=n, p=w / w.sum())


def _tokens(spec: dict, topic: int, length: int, vocab: int,
            rng: np.random.Generator) -> np.ndarray:
    lo, hi = spec["topics"][topic]["vocab_band"]
    a = int(lo * vocab)
    b = max(a + 1, int(hi * vocab))
    return rng.integers(a, b, size=length, dtype=np.int32)


def _build(spec: dict, due: np.ndarray, plens, olens, topics, vocab: int,
           rng: np.random.Generator) -> list[Arrival]:
    names = [t["name"] for t in spec["topics"]]
    return [
        Arrival(float(d), _tokens(spec, int(k), int(p), vocab, rng), int(o),
                names[int(k)])
        for d, p, o, k in zip(due, plens, olens, topics)
    ]


def window_arrivals(spec: dict, vocab: int, seed: int,
                    seconds: float) -> list[Arrival]:
    """The requests due from ``-ramp_seconds`` to the end of a window of
    ``seconds`` seconds, sorted by due time. The ramp's requests, due
    before the window opens, bring the engine to its steady state; the
    window's metrics count only the tokens they deliver inside it.
    ``backlog`` mixes put every request at t=0."""
    srng = _rng(spec["structure_seed"], 1)
    if spec["process"] == "backlog":
        due = np.zeros(int(spec["backlog"]))
    else:
        ramp = float(spec.get("ramp_seconds", 0.0))
        due = arrival_times(spec, ramp + seconds, srng) - ramp
    n = len(due)
    plens = _prompt_lengths(spec, n, srng)
    olens = _output_lengths(spec["output"], n, srng)
    shift = spec.get("shift")
    cut = n
    if shift is not None:
        cut = int(np.searchsorted(due, shift["at_fraction"] * seconds))
    topics = np.concatenate([
        _topics(spec["mix"], cut, srng),
        _topics(shift["mix"] if shift else spec["mix"], n - cut, srng),
    ])
    # the run's seed permutes the sizes and topics within each half
    rng = _rng(seed, 2)
    order = np.concatenate([rng.permutation(cut), cut + rng.permutation(n - cut)])
    return _build(spec, due, plens[order], olens[order], topics[order], vocab,
                  rng)


def warmup_arrivals(spec: dict, vocab: int, seed: int,
                    batch: int) -> list[Arrival]:
    """Set-up requests: ``batch`` of them, every prompt bucket of the mix
    in turn, the first topic mix, ``warmup_new_tokens`` new tokens each.
    Together they compile every shape the window will use and decode long
    enough for GEM's warm-up plan."""
    rng = _rng(seed, 3)
    buckets = list(spec["prompt_buckets"])
    plens = [buckets[i % len(buckets)] for i in range(batch)]
    topics = _topics(spec["mix"], batch, rng)
    olens = [int(spec["warmup_new_tokens"])] * batch
    return _build(spec, np.zeros(batch), plens, olens, topics, vocab, rng)
