"""The harness's correctness check, driven on the CPU at the tiny size
with the look for a chip skipped: a sound run is correct, the float8
control is judged not correct by the same rule, and each planted fault
makes ``correct`` false."""
import time

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import TINY_CELL, FakeChip

SEED = 2_147_483_659


def _alter_tokens(eng):
    """Every decoded token is altered where it is produced: the logits
    favour the token after the best one."""
    decode = eng._decode

    def bad(*args):
        logits, caches, aux = decode(*args)
        nxt = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
        logits = logits.at[jnp.arange(logits.shape[0]), nxt].add(1e3)
        return logits, caches, aux

    eng._decode = bad


def _state_unchanged(eng):
    """The decode step hands back the KV cache it was given."""
    decode = eng._decode

    def bad(*args):
        logits, _, aux = decode(*args)
        return logits, args[1], aux

    eng._decode = bad


def _swap_experts(eng):
    """Real experts 0 and 1 trade router-table entries in layer 0 (each
    of their ``expert_tp`` virtual slices) without their weights moving:
    a wrong placement."""
    tp = eng.config.expert_tp
    t = np.asarray(eng.placements).copy()
    rows = np.arange(2 * tp)
    t[0, rows] = t[0, np.roll(rows, tp)]
    eng.placements = jnp.asarray(t)


def _run(cell, **kw):
    return cell.run(TINY_CELL, SEED, 2.0, False, t_start=time.perf_counter(),
                    device=FakeChip(), log=lambda *a: None, **kw)


def test_sound_run_is_correct_and_control_fails(tiny):
    r = _run(tiny, control=True)
    (name, check), = [(n, c) for n, c in r["checks"].items() if n in r["stats"]]
    assert r["correct"], r["checks"]
    assert check["value"] <= check["limit"]
    assert r["control"][name] > 3 * check["limit"]
    assert r["control_correct"] is False
    assert r["checks"]["dropped_tokens"]["value"] == 0
    assert list(r)[-1] == "checks"
    # with --trace 0 the line carries the end-to-end metrics only
    assert set(r["metrics"]) == {m["name"] for m in tiny.load_benchmark()["end_to_end"]}


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged, _swap_experts])
def test_planted_fault_is_caught(tiny, fault):
    r = _run(tiny, breaker=fault)
    assert not r["correct"], r["checks"]
    assert any(c["value"] > c["limit"] for n, c in r["checks"].items()
               if n in r["stats"])
