"""Bring-up check of the serving path on TPU: ``python chip_smoke.py [--four-chips]``.

One chip (the default) serves granite-moe-3b-a800m at its full published
width and depth (bf16, random weights from ``--seed``) through
``ServingEngine`` with the Pallas MoE kernels and the GEM policy. GEM's
"devices" are a simulated 4-device fleet profile; the model itself runs on
the chip. Checks:

* the router and expert-FFN kernels against ``kernels/ref.py`` at granite
  widths: router ids exact, gates within ``GATE_TOL``, FFN within
  ``FFN_TOL`` (bf16);
* one MoE layer (layer 0's weights) on the same inputs agrees between the
  ``pallas`` and ``einsum`` backends within ``MOE_LAYER_TOL``, and a
  planted fault (two experts' router-table entries swapped) exceeds it;
* the einsum decode executable holds no f32 copy of an expert weight;
* 8 seeded requests (prompt lengths 64 and 512, 16 new tokens each) all
  finish, and every decode step's logits are finite;
* the GEM replan and its placement apply land mid-run without retracing
  the decode executable (``jit_trace_counts["decode"] == 1``);
* the compiled decode executable holds Mosaic kernels (``tpu_custom_call``),
  i.e. the kernels did not run interpreted;
* the first decode step's logits match the same requests served with the
  ``einsum`` backend within ``LOGITS_TOL`` (relative L2), and the planted
  fault in layer 0 exceeds it.

``--four-chips`` runs only the cross-chip path and what it is compared
with. Granite at full width and depth in bf16 on a (1, 4) mesh, weights
placed by their specs (80 virtual experts, 20 per chip): each chip holds a
quarter of the expert weights, the einsum decode executable holds no f32
copy of one, the requests are served with the GEM migration run as
collectives, and the migrated expert weights equal the host gather bit for
bit. Then, in float32 with ``MESH_LAYERS`` layers, the mesh's first-step
logits agree with the same requests under ``host_policy()`` on one chip
within ``MESH_LOGITS_TOL``.

It exits non-zero, printing no result, when JAX finds no TPU or a check
fails. The times it prints are wall-clock set-up and compile times, not a
benchmark. The last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "granite-moe-3b-a800m"
PROMPT_LENS = (64, 512)
FOUR_CHIP_PROMPT_LEN = 128
NUM_REQUESTS = 8
NEW_TOKENS = 16
TRACE_LENGTH = 4  # GEM replans after 4 of the 16 decode steps
# f32 router gates: the kernel's and XLA's exp may differ in the last ulps
GATE_TOL = 1e-5
# bf16 expert outputs: ≈2.5 bf16 ulps at |y|≈1 (F-blocked f32 accumulation
# in the kernel, one f32 dot in the reference, both rounded to bf16)
FFN_TOL = 2e-2
# relative L2 of one MoE layer's output, pallas vs einsum on the same bf16
# inputs (granite widths). Both backends select on the same f32 logits, so
# what is left is bf16 rounding of the expert outputs (≈3.5e-3 on a v5e);
# two swapped experts move the output by 0.12 to 0.33
MOE_LAYER_TOL = 1e-2
# relative L2 of first-step logits, pallas vs einsum in bf16 over 32 layers:
# the per-layer rounding noise flips near-tied experts in later layers and
# grows to ≈2.4e-2 with identical routers; two swapped experts in layer 0
# move the logits by ≈0.12. A fault in a later layer moves them less than
# the noise does, and is caught by the one-layer check above (every layer
# runs the same code)
LOGITS_TOL = 5e-2
# the (1, 4) mesh against one chip runs in float32: a partitioned program
# reduces its partial sums in its own order, which in bf16 moves a random-
# weight model's logits by several percent and would blur the check. The
# bound leaves room for one near-tied expert flipping; a wrong shard or
# collective moves the logits by O(1)
MESH_LOGITS_TOL = 2e-2
# float32 at full depth would not fit the one-chip reference (13 GB of
# weights on a 16 GB chip): the four-chip check keeps 8 of the 32 layers
MESH_LAYERS = 8


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  ok: {what}")


def stamp(what: str, t0: float) -> None:
    print(f"  {what}: {time.perf_counter() - t0:.2f} s wall "
          "(set-up and compile, not a benchmark)")


def make_prompts(vocab: int, lens, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, vocab, size=lens[i % len(lens)]).astype(np.int32)
        for i in range(NUM_REQUESTS)
    ]


def bytes_in_use(device) -> int:
    return device.memory_stats()["bytes_in_use"]


def rel_l2(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check_kernels(cfg, seed: int) -> None:
    """Router and expert FFN against the jnp oracles at ``cfg``'s widths."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.moe_gemm import SKINNY_BLOCK_C, moe_ffn_pallas
    from repro.kernels.ref import moe_ffn_ref, topk_router_ref
    from repro.kernels.topk_router import topk_router_pallas

    E, k = cfg.num_experts, cfg.experts_per_token
    key = jax.random.PRNGKey(seed)
    for T in (NUM_REQUESTS, PROMPT_LENS[-1]):
        logits = jax.random.normal(jax.random.fold_in(key, T), (T, E))
        gates, ids, psum, counts = topk_router_pallas(
            logits, k, with_stats=True)
        plain_gates, plain_ids = topk_router_pallas(logits, k)
        ref_gates, ref_ids = topk_router_ref(logits, k)
        ref_ids = np.asarray(ref_ids)
        check(np.array_equal(np.asarray(ids), ref_ids)
              and np.array_equal(np.asarray(plain_ids), ref_ids),
              f"router ids == ref at T={T}, E={E}, k={k}")
        gerr = max(float(jnp.max(jnp.abs(g - ref_gates)))
                   for g in (gates, plain_gates))
        check(gerr <= GATE_TOL, f"router gates max|d|={gerr:.2e} <= {GATE_TOL}")
        check(np.array_equal(np.asarray(counts),
                             np.bincount(ref_ids.ravel(), minlength=E)),
              f"router counts == ref at T={T}")
        perr = float(jnp.max(jnp.abs(
            psum - jax.nn.softmax(logits, axis=-1).sum(axis=0))))
        check(perr <= GATE_TOL * T, f"router probs_sum max|d|={perr:.2e}")

    Ev = E * cfg.expert_tp
    D, Fv = cfg.d_model, cfg.expert_d_ff // cfg.expert_tp
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    s_in, s_out = D ** -0.5, cfg.expert_d_ff ** -0.5
    wg = (jax.random.normal(ks[1], (Ev, D, Fv)) * s_in).astype(dt)
    wu = (jax.random.normal(ks[2], (Ev, D, Fv)) * s_in).astype(dt)
    wd = (jax.random.normal(ks[3], (Ev, Fv, D)) * s_out).astype(dt)
    for C in (SKINNY_BLOCK_C, cfg.pallas_block_c):
        x = jax.random.normal(jax.random.fold_in(ks[0], C), (Ev, C, D)).astype(dt)
        got = moe_ffn_pallas(x, wg, wu, wd, block_c=C,
                             block_f=cfg.pallas_block_f)
        want = moe_ffn_ref(x, wg, wu, wd)
        err = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
        bound = FFN_TOL * (1.0 + jnp.abs(want.astype(jnp.float32)))
        check(bool(jnp.all(err <= bound)),
              f"moe_ffn (E_v={Ev}, C={C}, D={D}, F={Fv}, block_c={C}) "
              f"== ref within {FFN_TOL}: max|d|={float(err.max()):.3e}")


def first_step(eng, prompts) -> np.ndarray:
    """Submit ``prompts`` and run one engine step (all prefills plus the
    first decode); returns that decode's logits over the real vocab."""
    for p in prompts:
        eng.submit(p, max_new_tokens=NEW_TOKENS)
    out = eng.step()
    return np.asarray(out["logits"], np.float32)[: len(prompts),
                                                 : eng.config.vocab_size]


def engine_config(backend: str, **overrides):
    from repro.core import GEMConfig
    from repro.serving import EngineConfig

    return dataclasses.replace(EngineConfig(
        max_batch=NUM_REQUESTS, max_len=640,
        gem=GEMConfig(trace_length=TRACE_LENGTH, num_restarts=4),
        placement_policy="gem", moe_backend=backend,
    ), **overrides)


def serve_to_end(eng, first_logits: np.ndarray) -> None:
    """Finish the submitted requests; check outputs, the replan and the
    trace count."""
    check(bool(np.isfinite(first_logits).all()), "step 0 logits finite")
    replan_step = 0 if eng.placement_applied else None
    steps = 1
    while eng.scheduler.has_work() and steps < 10 * NEW_TOKENS:
        out = eng.step()
        if not np.isfinite(np.asarray(out["logits"], np.float32)).all():
            raise CheckFailed(f"non-finite logits at step {steps}")
        if replan_step is None and eng.placement_applied:
            replan_step = steps
        steps += 1
    check(len(eng.finished) == NUM_REQUESTS and all(
        len(r.generated) == NEW_TOKENS for r in eng.finished),
        f"{NUM_REQUESTS} requests finished with {NEW_TOKENS} tokens each "
        f"in {steps} steps, all logits finite")
    moves = sum(r["moves"] for r in eng.migration_records)
    check(replan_step is not None and 0 < replan_step < steps - 1,
          f"GEM replan over {eng.planner.num_devices} simulated devices "
          f"applied mid-run at step {replan_step} ({moves} expert moves)")
    counts = eng.jit_trace_counts
    check(counts["decode"] == 1,
          f"decode traced once across the replan: {counts}")


def swap_experts(tables, layer: int, tp: int):
    """A planted fault: real experts 0 and 1 trade router-table entries in
    ``layer`` of the (L, E_v) ``tables``, so each one's tokens reach the
    other's weights."""
    import jax.numpy as jnp

    rows = jnp.arange(2 * tp)
    return tables.at[layer, rows].set(tables[layer, jnp.roll(rows, tp)])


def check_moe_layer(cfg, params, seed: int) -> None:
    """One MoE layer (layer 0's weights) on the same inputs, pallas against
    einsum, and the planted fault that ``MOE_LAYER_TOL`` must catch."""
    import jax
    import jax.numpy as jnp

    from repro.models.moe import moe_layer
    from repro.sharding import host_policy

    p = jax.tree.map(lambda w: w[0], params["blocks"]["moe"])
    Ev = cfg.num_experts * cfg.expert_tp
    ident = jnp.arange(Ev, dtype=jnp.int32)[None]
    fault = swap_experts(ident, 0, cfg.expert_tp)

    def layer(backend, x, table):
        y, _ = jax.jit(lambda x, p, t: moe_layer(
            x, p, t, cfg, host_policy(), backend=backend))(x, p, table[0])
        return np.asarray(y, np.float32)

    for T in (NUM_REQUESTS, PROMPT_LENS[-1]):
        x = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), T),
                              (1, T, cfg.d_model)).astype(cfg.dtype)
        want = layer("einsum", x, ident)
        err = rel_l2(layer("pallas", x, ident), want)
        check(err <= MOE_LAYER_TOL,
              f"MoE layer at T={T}, pallas vs einsum: rel L2 {err:.3e} <= "
              f"{MOE_LAYER_TOL}")
        moved = rel_l2(layer("pallas", x, fault), want)
        check(moved > MOE_LAYER_TOL,
              f"MoE layer at T={T}, experts 0 and 1 swapped: rel L2 "
              f"{moved:.3e} > {MOE_LAYER_TOL}")


def one_chip(cfg, seed: int) -> None:
    import jax

    from repro.launch.hlo_analysis import arrays_shaped, expert_weight_shapes
    from repro.launch.serve import build_engine, init_placed_params
    from repro.sharding import host_policy

    print("[kernels] granite widths vs kernels/ref.py")
    t0 = time.perf_counter()
    check_kernels(cfg, seed)
    stamp("kernel checks", t0)

    policy = host_policy()
    prompts = make_prompts(cfg.vocab_size, PROMPT_LENS, seed)
    t0 = time.perf_counter()
    params = init_placed_params(cfg, policy, seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    stamp(f"{cfg.name} {n_params / 1e9:.2f}B params ({cfg.dtype}) on chip", t0)

    print("[moe layer] pallas vs einsum on the same inputs")
    t0 = time.perf_counter()
    check_moe_layer(cfg, params, seed)
    stamp("MoE layer checks", t0)

    print("[einsum] reference first decode step")
    t0 = time.perf_counter()
    ref = build_engine(cfg, policy, engine_config("einsum"), params=params)
    ref_logits = first_step(ref, prompts)
    check(not arrays_shaped(ref.decode_hlo(), "f32",
                            expert_weight_shapes(cfg)),
          "einsum decode HLO holds no f32 copy of an expert weight")
    del ref
    faulty = build_engine(cfg, policy, engine_config("einsum"), params=params)
    faulty.placements = swap_experts(faulty.placements, 0, cfg.expert_tp)
    moved = rel_l2(first_step(faulty, prompts), ref_logits)
    del faulty
    check(moved > LOGITS_TOL,
          f"experts 0 and 1 swapped in layer 0 move the first-step logits: "
          f"rel L2 {moved:.3e} > {LOGITS_TOL}")
    stamp("einsum prefills + first decodes", t0)

    print("[pallas] serve through the engine")
    t0 = time.perf_counter()
    eng = build_engine(cfg, policy, engine_config("pallas"), params=params)
    del params  # the engine owns the weights; migrations donate them
    logits = first_step(eng, prompts)
    err = rel_l2(logits, ref_logits)
    check(err <= LOGITS_TOL,
          f"first-step logits pallas vs einsum: rel L2 {err:.3e} <= "
          f"{LOGITS_TOL}")
    serve_to_end(eng, logits)
    stamp("pallas serve", t0)
    t0 = time.perf_counter()
    check("tpu_custom_call" in eng.decode_hlo(),
          "decode executable contains tpu_custom_call (Mosaic kernels)")
    stamp("decode HLO", t0)


def four_chips(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.hlo_analysis import arrays_shaped, expert_weight_shapes
    from repro.launch.mesh import make_host_mesh, policy_for
    from repro.launch.serve import build_engine, init_placed_params
    from repro.models.moe import apply_placement
    from repro.sharding import host_policy

    n = len(jax.devices())
    check(n == 4, f"four devices visible: {n}")
    policy = policy_for(make_host_mesh(1, 4), step_kind="decode")
    prompts = make_prompts(cfg.vocab_size, (FOUR_CHIP_PROMPT_LEN,), seed)
    # dense KV on both sides: the paged pool does not shard on a mesh
    ecfg = engine_config("pallas", kv_mode="dense", max_len=256,
                         migration_via="collective")

    print(f"[mesh] (1, 4) mesh, {cfg.num_layers} layers in {cfg.dtype}, "
          "weights placed by their specs")
    t0 = time.perf_counter()
    params = init_placed_params(cfg, policy, seed)
    stamp("sharded init", t0)
    experts = ("w_gate", "w_up", "w_down")
    moe = params["blocks"]["moe"]
    Ev = cfg.num_experts * cfg.expert_tp
    for name in experts:
        shards = moe[name].addressable_shards
        check(len({s.device for s in shards}) == 4 and all(
            s.data.shape[1] == Ev // 4 for s in shards),
            f"{name}: {Ev // 4} of {Ev} virtual experts on each of 4 chips")
    expert_bytes = sum(moe[name].nbytes for name in experts)
    other_bytes = sum(x.nbytes for x in jax.tree.leaves(params)) - expert_bytes
    in_use = [bytes_in_use(d) for d in jax.devices()]
    # a quarter of the experts plus at most every other weight (replicated)
    # and 64 MiB of runtime buffers
    check(all(expert_bytes / 4 <= b <= expert_bytes / 4 + other_bytes + 2**26
              for b in in_use),
          f"bytes in use per chip {in_use}: a quarter of the {expert_bytes} "
          f"expert bytes each (other weights {other_bytes} bytes)")
    t0 = time.perf_counter()
    hlo = build_engine(cfg, policy, dataclasses.replace(ecfg, moe_backend="einsum"),
                       params=params).decode_hlo()
    check(not arrays_shaped(hlo, "f32", expert_weight_shapes(cfg, 4)),
          "einsum decode HLO on the mesh holds no f32 copy of an expert "
          "weight")
    stamp("einsum decode compile", t0)
    before = {name: jnp.copy(moe[name]) for name in experts}

    t0 = time.perf_counter()
    eng = build_engine(cfg, policy, ecfg, params=params)
    del params, moe
    serve_to_end(eng, first_step(eng, prompts))
    stamp("mesh serve", t0)
    via = {r["via"] for r in eng.migration_records}
    payload = sum(r.get("payload_bytes", 0) for r in eng.migration_records)
    check(via == {"collective"},
          f"migration ran as collectives over the model axis "
          f"({payload} bytes across chips)")
    s2e = jnp.asarray(np.stack([p.slot_to_expert()
                                for p in eng.current_placements]))
    want = apply_placement(before, s2e)
    got = eng.params["blocks"]["moe"]
    check(all(bool(jnp.array_equal(got[name], want[name]))
              for name in experts),
          "expert weights after the collective migration == host gather, "
          "bit for bit")
    del eng, before, want, got

    small = dataclasses.replace(cfg, dtype="float32", num_layers=MESH_LAYERS)
    print(f"[logits] (1, 4) mesh vs one chip under host_policy(), "
          f"{small.num_layers} layers in {small.dtype}")
    t0 = time.perf_counter()
    mesh_logits = first_step(build_engine(small, policy, ecfg, seed=seed),
                             prompts)
    host = build_engine(small, host_policy(),
                        dataclasses.replace(ecfg, migration_via="host"),
                        seed=seed)
    host_logits = first_step(host, prompts)
    stamp("both inits + first decodes", t0)
    err = rel_l2(mesh_logits, host_logits)
    check(err <= MESH_LOGITS_TOL,
          f"first-step logits (1, 4) mesh vs one chip: rel L2 {err:.3e} "
          f"<= {MESH_LOGITS_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the cross-chip path and its one-chip reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import model_config

    cache = enable_compile_cache()
    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}); "
          f"compile cache: {cache}")
    cfg = model_config(ARCH)
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(cfg, args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stamp("total", t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
