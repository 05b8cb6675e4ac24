"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the harness contract:
``us_per_call`` is the mean wall time of the benchmark's core operation;
``derived`` carries the headline quantity the paper reports for that
table/figure. A JSON dump of every row lands in results/bench.json.

Run: ``PYTHONPATH=src python -m benchmarks.run [--moe-backend pallas]``

``--moe-backend`` selects the MoE data-plane backend (einsum | pallas |
dense_ref) for the benches that execute the real JAX model; the
simulator-only figure benches are backend-independent and ignore it.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time


def _timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, (time.perf_counter() - t0) * 1e6


def bench_fig02_utilization():
    from . import fig02_utilization as m

    (rows, extra), us = _timed(m.run)
    s = m.summarize(rows, extra)
    return rows, us / len(rows), (
        f"max_over_uniform={s['max_over_uniform_peak']:.1f}x;"
        f"top8_overlap={s['mean_top8_overlap']:.2f}"
    )


def bench_fig10_trace_length():
    from . import fig10_trace_length as m

    rows, us = _timed(m.run)
    s = m.summarize(rows)
    sat = all(v["saturated_by_16"] for v in s.values())
    worst1 = min(v["at_1"] for v in s.values())
    return rows, us / len(rows), (
        f"saturates_by_16={sat};min_reduction_at_T1={worst1:.1f}pct"
    )


def bench_fig15_e2e():
    from . import fig15_e2e as m

    rows, us = _timed(m.run)
    s = m.summarize(rows)
    return rows, us / len(rows), (
        f"high_mean={s['high']['mean_pct']:.1f}pct;"
        f"high_max={s['high']['max_pct']:.1f}pct;"
        f"moderate_mean={s['moderate']['mean_pct']:.1f}pct;"
        f"low_mean={s['low']['mean_pct']:.1f}pct"
    )


def bench_fig16_tpot():
    from . import fig16_tpot as m

    rows, us = _timed(m.run, ("high",))
    s = m.summarize(rows)
    return rows, us / len(rows), (
        f"p90_mean={s['p90_mean_pct']:.1f}pct;p90_max={s['p90_max_pct']:.1f}pct;"
        f"mean_vs_p99_spread={s['mean_vs_p99_spread_pts']:.2f}pts"
    )


def bench_fig17_policies():
    from . import fig17_policies as m

    (rows, _info), us = _timed(m.run)
    s = m.summarize(rows)
    return rows, us / len(rows), (
        f"gem_vs_linear={s['gem_vs_linear_pct']:.1f}pct;"
        f"gem_vs_eplb={s['gem_vs_eplb_pts']:.1f}pts;"
        f"drains_slow={s['gem_drains_slow_device']}"
    )


def bench_fig18_profiling():
    from . import fig18_profiling as m

    rows, us = _timed(m.run)
    s = m.summarize(rows)
    return rows, us / len(rows), (
        f"speedup={s['min_speedup']:.0f}x..{s['max_speedup']:.0f}x;"
        f"fast_minutes={s['fast_minutes_range'][0]:.1f}.."
        f"{s['fast_minutes_range'][1]:.1f}"
    )


def bench_fig19_scale():
    from . import fig19_scale as m

    rows, us = _timed(m.run)
    s = m.summarize(rows)
    return rows, us / len(rows), (
        f"gap_N4={s['gap_at_4_pct']:.1f}pct;gap_N64={s['gap_at_64_pct']:.1f}pct;"
        f"monotone={s['monotone']}"
    )


def bench_tab_convergence():
    from . import tab_convergence as m

    rows, us = _timed(m.run)
    s = m.summarize(rows)
    return rows, us / len(rows), (
        f"max_swaps={s['max_swaps_any_model']};"
        f"under_18={s['under_paper_bound_18']};"
        f"map_s_per_layer={s['max_mapping_s_per_layer']:.2f}"
    )


def bench_kernels(moe_backend: str = "einsum"):
    """MoE FFN kernel micro-bench on this host. einsum times the jit'd jnp
    oracle; pallas runs the fused kernel (interpret mode off-TPU — numbers
    validate the path, not TPU speed) and reports parity vs the oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import moe_ffn
    from repro.kernels.ref import moe_ffn_ref

    key = jax.random.PRNGKey(0)
    # interpret mode executes the kernel body op-by-op: keep pallas dims small
    E, C, D, F = (8, 256, 512, 1024) if moe_backend == "einsum" else (4, 128, 128, 256)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (E, C, D), jnp.float32)
    wg = jax.random.normal(ks[1], (E, D, F), jnp.float32) * 0.05
    wu = jax.random.normal(ks[2], (E, D, F), jnp.float32) * 0.05
    wd = jax.random.normal(ks[3], (E, F, D), jnp.float32) * 0.05
    flops = 6 * E * C * D * F
    if moe_backend == "pallas":
        got = moe_ffn(x, wg, wu, wd, block_c=128, block_f=256)
        err = float(
            np.abs(np.asarray(got) - np.asarray(moe_ffn_ref(x, wg, wu, wd))).max()
        )
        t0 = time.perf_counter()
        moe_ffn(x, wg, wu, wd, block_c=128, block_f=256).block_until_ready()
        us = (time.perf_counter() - t0) * 1e6
        return [], us, f"pallas_interpret_max_abs_err={err:.2e}"
    ffn = jax.jit(moe_ffn_ref)
    ffn(x, wg, wu, wd).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        ffn(x, wg, wu, wd).block_until_ready()
    us = (time.perf_counter() - t0) / 5 * 1e6
    return [], us, f"moe_ffn_ref_gflops={flops / (us * 1e-6) / 1e9:.1f}"


def bench_moe_layer_backend(moe_backend: str = "einsum"):
    """Data-plane wiring check: the smoke-Mixtral MoE layer under the
    selected backend vs the einsum reference (max |Δ| must be ~fp32 eps)."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.models.moe import identity_placement, init_moe, moe_layer
    from repro.sharding import host_policy

    cfg = dc.replace(get_smoke_config("mixtral-8x7b"), capacity_factor=8.0)
    policy = host_policy()
    params, _ = init_moe(
        jax.random.PRNGKey(0), cfg, num_layers=1, dtype=jnp.float32,
        policy=policy,
    )
    lp = jax.tree.map(lambda t: t[0], params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    table = identity_placement(cfg, 1)[0]
    y_ref, _ = moe_layer(x, lp, table, cfg, policy, backend="einsum")
    y, aux = moe_layer(x, lp, table, cfg, policy, backend=moe_backend)  # warmup
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    y, aux = moe_layer(x, lp, table, cfg, policy, backend=moe_backend)
    jax.block_until_ready(y)
    us = (time.perf_counter() - t0) * 1e6
    err = float(np.abs(np.asarray(y) - np.asarray(y_ref)).max())
    return [], us, (
        f"backend={moe_backend};max_abs_err_vs_einsum={err:.2e};"
        f"dropped={float(aux['dropped']):.3f}"
    )


def bench_moe_layer_shard_map(moe_backend: str = "einsum"):
    """Per-shard kernel dispatch wiring check: the smoke-Mixtral MoE layer
    under a real host mesh (all local devices) vs the einsum reference. With
    ``--moe-backend pallas`` this exercises the shard_map path — the fused
    kernels on each device's (E_v/mm, C, D) shard — which must match einsum
    to ~fp32 eps and produce identical expert_counts."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.models.moe import identity_placement, init_moe, moe_layer
    from repro.sharding.policy import ShardingPolicy

    nd = len(jax.devices())
    data = 2 if nd % 2 == 0 and nd > 1 else 1
    model = nd // data
    mesh = make_host_mesh(data, model)
    policy = ShardingPolicy(mesh=mesh)
    cfg = dc.replace(get_smoke_config("mixtral-8x7b"), capacity_factor=8.0)
    params, _ = init_moe(
        jax.random.PRNGKey(0), cfg, num_layers=1, dtype=jnp.float32,
        policy=policy,
    )
    lp = jax.tree.map(lambda t: t[0], params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    table = identity_placement(cfg, 1)[0]
    with mesh:
        y_ref, aux_ref = moe_layer(x, lp, table, cfg, policy, backend="einsum")
        y, aux = moe_layer(x, lp, table, cfg, policy, backend=moe_backend)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        y, aux = moe_layer(x, lp, table, cfg, policy, backend=moe_backend)
        jax.block_until_ready(y)
    us = (time.perf_counter() - t0) * 1e6
    err = float(np.abs(np.asarray(y) - np.asarray(y_ref)).max())
    counts_eq = bool(
        np.array_equal(
            np.asarray(aux["expert_counts"]),
            np.asarray(aux_ref["expert_counts"]),
        )
    )
    return [], us, (
        f"backend={moe_backend};mesh={data}x{model};"
        f"max_abs_err_vs_einsum={err:.2e};counts_equal={counts_eq}"
    )


def bench_roofline():
    from . import roofline as m

    if not os.path.exists("results/dryrun.json"):
        return [], 0.0, "missing_results/dryrun.json_run_dryrun_first"
    (rows, summary), us = _timed(m.run)
    os.makedirs("results", exist_ok=True)
    with open("results/roofline.md", "w") as f:
        f.write(m.to_markdown(rows))
    return rows, us / max(len(rows), 1), (
        f"cells_ok={summary['cells_ok']};fits_all={summary['all_fit_16gb']};"
        f"dominant={summary['dominant_hist']}"
    )


BENCHES = [
    ("fig02_expert_utilization", bench_fig02_utilization),
    ("fig10_trace_length", bench_fig10_trace_length),
    ("fig15_e2e_latency", bench_fig15_e2e),
    ("fig16_tpot_tail", bench_fig16_tpot),
    ("fig17_mapping_policies", bench_fig17_policies),
    ("fig18_profiling_cost", bench_fig18_profiling),
    ("fig19_variability_at_scale", bench_fig19_scale),
    ("tab_search_convergence", bench_tab_convergence),
    ("kernel_moe_ffn", bench_kernels),
    ("moe_layer_backend", bench_moe_layer_backend),
    ("moe_layer_shard_map", bench_moe_layer_shard_map),
    ("roofline_from_dryrun", bench_roofline),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--moe-backend", default="einsum",
                    choices=("einsum", "pallas", "dense_ref"))
    ap.add_argument("--only", default="",
                    help="substring filter on benchmark names")
    args = ap.parse_args(argv)
    os.makedirs("results", exist_ok=True)
    all_rows = {}
    if args.only and os.path.exists("results/bench.json"):
        # a filtered run updates, rather than replaces, prior full results
        with open("results/bench.json") as f:
            all_rows = json.load(f)
    print("name,us_per_call,derived")
    failed = []
    for name, fn in BENCHES:
        if args.only and args.only not in name:
            continue
        kwargs = (
            {"moe_backend": args.moe_backend}
            if "moe_backend" in inspect.signature(fn).parameters
            else {}
        )
        try:
            rows, us, derived = fn(**kwargs)
            all_rows[name] = rows
            print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception as e:  # report every bench, then fail the run
            failed.append(name)
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}", flush=True)
    with open("results/bench.json", "w") as f:
        json.dump(all_rows, f, indent=1, default=str)
    if failed:
        print(f"{len(failed)} benchmark(s) failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
