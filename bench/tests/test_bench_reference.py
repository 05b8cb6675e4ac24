"""The plain reference against the program, at the tiny size on the CPU."""
import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest
import reference
from conftest import DATA

SEED = 2_900_000_017


def _tiny(dtype="float32"):
    cfg = json.loads((DATA / "tiny-moe.json").read_text())
    cfg["program"]["dtype"] = dtype
    return cfg


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_weights_equal_the_programs(dtype):
    import cell
    from repro.launch.serve import init_placed_params
    from repro.sharding import host_policy

    cfg = _tiny(dtype)
    model, _ = cell.program(cfg)
    got = init_placed_params(model, host_policy(), SEED % 2**32)
    want = reference.init_weights(reference.dims(cfg), SEED)
    b = got["blocks"]
    pairs = {"embed": got["embed"], "final_norm": got["final_norm"],
             "ln1": b["ln1"], "ln2": b["ln2"], "router": b["moe"]["router"],
             **{k: b["attn"][k] for k in ("wq", "wk", "wv", "wo")},
             **{k: b["moe"][k] for k in ("w_gate", "w_up", "w_down")}}
    assert set(pairs) == set(want)
    for name, arr in pairs.items():
        assert arr.dtype == want[name].dtype, name
        assert np.array_equal(np.asarray(arr), np.asarray(want[name])), name


def test_reference_matches_prefill_then_decode():
    """The engine's decode logits for each served position equal the
    reference's full forward pass over the same tokens (float32)."""
    import cell
    from repro.launch.serve import build_engine, init_placed_params
    from repro.sharding import host_policy

    cfg = _tiny()
    model, ecfg = cell.program(cfg)
    params = init_placed_params(model, host_policy(), SEED % 2**32)
    eng = build_engine(model, host_policy(), ecfg, params=params)
    ctx = cell.LayerContext(dims=reference.dims(cfg), peak={"flops": 1.0})
    hooks = cell.Hooks(eng, ctx)
    seen = {}  # uid -> list of decode logits rows
    decode = eng._decode

    def spy(*args):
        out = decode(*args)
        rows = np.asarray(out[0])
        for slot, req in eng.scheduler.active.items():
            if eng.installed[slot]:
                seen.setdefault(req.uid, []).append(rows[slot, : model.vocab_size])
        return out

    eng._decode = spy
    rng = np.random.default_rng(0)
    prompts = {eng.submit(rng.integers(0, 128, size=n), 6): n for n in (8, 13, 21)}
    eng.run()
    d = reference.dims(cfg)
    w = reference.init_weights(d, SEED)
    for req in eng.finished:
        served = [hooks.first_fed[req.uid]] + list(req.generated)
        fed, at = reference.served_sequence(req.prompt, served)
        toks = np.zeros(64, np.int32)
        toks[: len(fed)] = fed
        ref = np.asarray(reference.logits(w, jnp.asarray(toks), d))
        got = np.stack(seen[req.uid])  # decode j predicts position P + j
        want = ref[at[1:]]
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
        assert len(req.prompt) == prompts[req.uid]


def test_control_rounds_to_float8():
    w = jnp.asarray(np.random.default_rng(1).normal(size=(64, 32)), jnp.float32)
    q = reference.fp8(w, 0)
    assert not np.array_equal(np.asarray(q), np.asarray(w))
    rel = float(jnp.max(jnp.abs(q - w) / jnp.max(jnp.abs(w), 0)))
    assert 0 < rel <= 2.0 ** -4  # e4m3: 3 mantissa bits
    # one scale per output column: each column's largest entry is exact
    i = jnp.argmax(jnp.abs(w), 0)
    assert np.allclose(np.asarray(q[i, jnp.arange(32)]),
                       np.asarray(w[i, jnp.arange(32)]), rtol=1e-6)


def test_gap_of_the_best_token_is_zero():
    d = reference.dims(_tiny())
    w = reference.init_weights(d, 5)
    prompt = np.arange(10, dtype=np.int32)
    toks = np.zeros(64, np.int32)
    toks[:10] = prompt
    best = int(np.argmax(np.asarray(reference.logits(w, jnp.asarray(toks), d))[9]))
    gaps = reference.position_gaps(w, d, [(prompt, [best])], 64)
    assert [g.tolist() for g in gaps] == [[0.0]]
    assert reference.gap_stats(gaps) == {"widest_logit_gap": 0.0,
                                         "mean_logit_gap": 0.0,
                                         "off_best_pct": 0.0}
    worst = int(np.argmin(np.asarray(reference.logits(w, jnp.asarray(toks), d))[9]))
    gaps = reference.position_gaps(w, d, [(prompt, [worst])], 64)
    assert gaps[0][0] > 0.0
    assert reference.gap_stats(gaps)["off_best_pct"] == 100.0
    assert dataclasses.is_dataclass(d)
