import numpy as np
import pytest
import traffic

SEED = 3_000_000_123


# a backlog mix, every request due at t=0; no cell offers one yet
BACKLOG = {"process": "backlog", "backlog": 384, "structure_seed": 20261017,
           "prompt_buckets": [512], "output": {"fixed": 64},
           "topics": [{"name": "topic-a", "vocab_band": [0.0, 0.5]}],
           "mix": [1.0]}


def _spec(name):
    return BACKLOG if name == "offline-backlog" else traffic.load(name)


@pytest.mark.parametrize("name", ["chat-shift-1.1rps", "offline-backlog"])
def test_same_seed_same_schedule(name):
    a = traffic.window_arrivals(_spec(name), 49155, SEED, 40.0)
    b = traffic.window_arrivals(_spec(name), 49155, SEED, 40.0)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new_tokens == y.max_new_tokens
        assert x.topic == y.topic and np.array_equal(x.prompt, y.prompt)


def test_seeds_offer_the_same_work_in_another_order():
    spec = _spec("chat-shift-1.1rps")
    a = traffic.window_arrivals(spec, 49155, 1, 40.0)
    b = traffic.window_arrivals(spec, 49155, 2, 40.0)
    assert [x.due for x in a] == [x.due for x in b]
    sizes = lambda s: sorted((len(x.prompt), x.max_new_tokens, x.topic) for x in s)
    assert sizes(a) == sizes(b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def test_chat_shift_mix_flips_at_the_midpoint():
    spec = _spec("chat-shift-1.1rps")
    arr = traffic.window_arrivals(spec, 49155, 5, 40.0)
    first = [x.topic for x in arr if x.due < 20.0]
    second = [x.topic for x in arr if x.due >= 20.0]
    share = lambda ts: ts.count("topic-a") / len(ts)
    assert share(first) > 0.6 and share(second) < 0.4
    for x in arr:
        lo, hi = (0, 24577) if x.topic == "topic-a" else (24577, 49155)
        assert lo <= x.prompt.min() and x.prompt.max() < hi
        assert len(x.prompt) in (128, 256, 512, 1024)
        assert 16 <= x.max_new_tokens <= 256
        assert -spec["ramp_seconds"] <= x.due < 40.0


def test_backlog_is_due_at_once():
    arr = traffic.window_arrivals(_spec("offline-backlog"), 49155, 9, 40.0)
    assert len(arr) == 384 and all(x.due == 0.0 for x in arr)
    assert {(len(x.prompt), x.max_new_tokens) for x in arr} == {(512, 64)}


def test_warmup_covers_every_bucket():
    spec = _spec("chat-shift-1.1rps")
    warm = traffic.warmup_arrivals(spec, 49155, 4, 32)
    assert len(warm) == 32
    assert {len(x.prompt) for x in warm} == set(spec["prompt_buckets"])


def test_bursts_at_the_cells_rate():
    """The window offers exactly the cell's rate, in bursts: the busiest
    ten seconds hold well over the mean."""
    spec = dict(_spec("chat-shift-1.1rps"), structure_seed=11)
    t = traffic.arrival_times(spec, 2000.0, np.random.default_rng(0))
    rate = spec["rate_rps"]
    assert len(t) == round(rate * 2000.0)
    assert 0 <= t.min() and t.max() < 2000.0 and np.all(np.diff(t) >= 0)
    per_10s = np.bincount((t // 10).astype(int), minlength=200)
    assert np.percentile(per_10s, 95) > 1.5 * rate * 10
