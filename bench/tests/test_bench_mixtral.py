"""The mixtral configuration's paths at a tiny size on the CPU: the plain
reference against the program, the prefill's in-place read of the stacked
expert weights, the layer-at-a-time migration apply, and the prefill
kernel's roofline arithmetic."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import reference
from conftest import DATA

SEED = 2_900_000_041
CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _tiny(dtype="float32"):
    cfg = json.loads((DATA / "tiny-mixtral.json").read_text())
    cfg["program"]["dtype"] = dtype
    return cfg


def test_tiny_has_the_served_configurations_shape():
    """Every key of the tiny configuration that is not a size is the
    served configuration's: untied, 8 experts, top-2, two virtual experts
    an expert, rope 1e6, eps 1e-5, no window, the same engine."""
    tiny = _tiny()
    real = json.loads((CONFIGS / "mixtral-8x7b-4l.json").read_text())
    for key in ("tie_word_embeddings", "num_local_experts",
                "num_experts_per_tok", "rope_theta", "rms_norm_eps",
                "sliding_window"):
        assert tiny[key] == real[key], key
    assert tiny["tie_word_embeddings"] is False
    for key in ("moe_backend", "expert_tp", "capacity_factor",
                "decode_capacity_factor", "family"):
        assert tiny["program"][key] == real["program"][key], key
    for key in ("decode_mode", "kv_mode", "placement_policy", "online"):
        assert tiny["engine"][key] == real["engine"][key], key


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
    pairs = {"embed": got["embed"], "lm_head": got["lm_head"],
             "final_norm": got["final_norm"],
             "ln1": b["ln1"], "ln2": b["ln2"], "router": b["moe"]["router"],
             **{k: b["attn"][k] for k in ("wq", "wk", "wv", "wo")},
             **{k: b["moe"][k] for k in ("w_gate", "w_up", "w_down")}}
    assert set(pairs) == set(want)
    for name, arr in pairs.items():
        assert arr.dtype == want[name].dtype, name
        assert np.array_equal(np.asarray(arr), np.asarray(want[name])), name


def test_reference_matches_prefill_then_decode():
    """The engine's decode logits for each served position equal the
    reference's full forward pass over the same tokens (float32), with
    the untied output head."""
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
    rng = np.random.default_rng(3)
    prompts = {eng.submit(rng.integers(0, 128, size=n), 6): n for n in (9, 16, 23)}
    eng.run()
    d = reference.dims(cfg)
    w = reference.init_weights(d, SEED)
    assert len(eng.finished) == len(prompts)
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


@pytest.mark.parametrize("backend", ["einsum", "pallas"])
def test_prefill_in_place_read_is_bit_identical_to_sliced(backend,
                                                          monkeypatch):
    """The prefill hands the MoE layer the stacked expert weights and the
    layer index; its logits and caches are bit for bit those of the same
    prefill given each layer's own weights, under a non-identity router
    table."""
    import cell
    from repro.launch.serve import init_placed_params
    from repro.models import model as model_mod
    from repro.sharding import host_policy

    m, _ = cell.program(_tiny())
    m = dataclasses.replace(m, moe_backend=backend)
    policy = host_policy()
    params = init_placed_params(m, policy, SEED % 2**32)
    rng = np.random.default_rng(5)
    Ev = m.num_experts * m.expert_tp
    tables = jnp.asarray(np.stack(
        [rng.permutation(Ev) for _ in range(m.num_layers)]).astype(np.int32))
    batch = {"tokens": jnp.asarray(rng.integers(0, 128, size=(1, 21)),
                                   jnp.int32)}

    def run():
        return jax.jit(lambda p, b, t: model_mod.prefill(p, b, m, policy, t))(
            params, batch, tables)

    in_place = run()
    real, stacked = model_mod.moe_layer, []

    def sliced(x, p, *args, layer=None, **kw):
        stacked.append(layer is not None)
        if layer is not None:
            p = {**p, **{n: p[n][layer] for n in ("w_gate", "w_up", "w_down")}}
        return real(x, p, *args, layer=None, **kw)

    monkeypatch.setattr(model_mod, "moe_layer", sliced)
    want = run()
    assert stacked and all(stacked)  # the prefill gave it the stack
    for a, b in zip(jax.tree.leaves(in_place), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_migration_apply_by_layer_equals_the_whole_stack_gather():
    """The apply rewrites the stacks one layer at a time; on random row
    maps, permutations and maps with repeated rows (replica copies), it
    gives exactly the whole-stack gather, from one trace, and counts the
    bytes of the rows that moved, none for an identity map."""
    from repro.kernels.collective import MigrationExecutable
    from repro.telemetry import Telemetry

    rng = np.random.default_rng(31)
    L, S = 3, 16
    ws = [jnp.asarray(rng.normal(size=(L, S) + tail).astype(np.float32))
          for tail in ((4, 6), (4, 6), (6, 4))]
    tel = Telemetry()
    ex = MigrationExecutable(mesh=None, donate=False, telemetry=tel)
    whole = jax.vmap(lambda a, s: jnp.take(a, s, axis=0))
    maps = [np.stack([rng.permutation(S) for _ in range(L)]),
            np.stack([rng.permutation(S) for _ in range(L)]),
            rng.integers(0, S, size=(L, S)),
            rng.integers(0, S, size=(L, S))]
    for src in maps:
        src = src.astype(np.int32)
        got, _ = ex(src, None, *ws)
        for g, w in zip(got, ws):
            assert np.array_equal(np.asarray(g), np.asarray(whole(w, src)))
    assert ex.trace_count == 1
    assert tel.counter("jit.trace.migrate").value == 1
    row_bytes = sum(w.nbytes for w in ws) // (L * S)
    moved = sum(np.count_nonzero(m != np.arange(S)) for m in maps)
    assert tel.counter("migrate.expert_bytes").value == moved * row_bytes
    ex(np.tile(np.arange(S, dtype=np.int32), (L, 1)), None, *ws)
    assert tel.counter("migrate.expert_bytes").value == moved * row_bytes


def _served_dims():
    return reference.dims(json.loads(
        (CONFIGS / "mixtral-8x7b-4l.json").read_text()))


def test_prefill_ffn_least_time_by_hand():
    import work
    from prefill_roofline import prefill_ffn_least_s

    d = _served_dims()
    peak = work.peaks("TPU v5 lite")
    D, F, L = 4096, 14336, 4
    # few tokens: every expert's weights, read once per layer per prefill
    runs, tokens = 3, 3 * 128
    byts = runs * L * 8 * 3 * D * F * 2 + tokens * 2 * L * 2 * 2 * D * 2
    assert prefill_ffn_least_s(d, runs, tokens, peak) == pytest.approx(
        byts / 819e9, rel=1e-12)
    # many tokens: the routed pairs' products
    runs, tokens = 1, 8192
    flops = tokens * 2 * L * 6 * D * F
    assert prefill_ffn_least_s(d, runs, tokens, peak) == pytest.approx(
        flops / 197e12, rel=1e-12)


def test_prefill_ffn_roofline_reads_the_prefill_kernel_only():
    import cell
    import work
    import xplane
    from prefill_roofline import prefill_ffn_least_s, prefill_ffn_roofline

    ctx = cell.LayerContext(dims=_served_dims(),
                            peak=work.peaks("TPU v5 lite"))
    assert prefill_ffn_roofline(ctx) is None  # nothing traced
    ms = 1_000_000
    ctx.trace = xplane.Reduction(
        window_s=1.0, busy_s=0.5, devices=1,
        modules=[("jit__prefill_fn", 0, 40 * ms),
                 ("jit__decode_paged", 50 * ms, 70 * ms),
                 ("jit__prefill_fn", 100 * ms, 130 * ms)],
        ops=[("moe_ffn_pallas.3", 5 * ms, 25 * ms),
             ("moe_ffn_pallas.12", 55 * ms, 65 * ms),
             ("moe_ffn_pallas.3", 105 * ms, 125 * ms),
             ("fusion.1", 26 * ms, 30 * ms)],
        spans=[], gaps=[])
    assert prefill_ffn_roofline(ctx) is None  # no prompt token counted
    ctx.slice_prefill_tokens = 128 + 256
    least = prefill_ffn_least_s(ctx.dims, 2, 384, ctx.peak)
    assert prefill_ffn_roofline(ctx) == pytest.approx(100 * least / 0.040)
