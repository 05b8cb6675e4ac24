import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first: JAX locks the device count at first
init, and the production meshes (16×16 single-pod, 2×16×16 multi-pod) need
512 placeholder host devices. Nothing here allocates real arrays — inputs
are ShapeDtypeStructs and outputs are compile-time analyses.

Per cell we record:
  * ``memory_analysis``  — per-device argument/output/temp bytes (the "fits
    in 16 GB v5e HBM" proof),
  * ``cost_analysis``    — per-device HLO FLOPs + bytes accessed,
  * collective bytes     — parsed from the post-SPMD HLO text, summed operand
    sizes per collective kind (all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute),
and append everything to a JSON results file consumed by the roofline
benchmark and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  python -m repro.launch.dryrun --all                  # single-pod, 40 cells
  python -m repro.launch.dryrun --all --multi-pod      # 2-pod mesh
"""
import argparse
import json
import sys
import time
import traceback

import jax

from ..configs import ARCHS, SHAPES, get_config, shape_applicable
from ..configs.base import ModelConfig, ShapeSpec
from .mesh import make_production_mesh, policy_for
from .specs import input_specs

from .hlo_analysis import collective_stats, compute_stats


def build_step_fn(config: ModelConfig, shape: ShapeSpec, policy):
    from ..models.model import decode_step, prefill
    from ..training.optimizer import AdamWConfig
    from ..training.train_step import make_train_step

    if shape.kind == "train":
        ts = make_train_step(config, policy, AdamWConfig(), remat=True)

        def train_fn(state, batch, placements=None):
            return ts(state, batch, placements)

        return train_fn, ("state",)
    if shape.kind == "prefill":
        def prefill_fn(params, batch, placements=None):
            return prefill(params, batch, config, policy, placements)

        return prefill_fn, ()
    if shape.kind == "decode":
        def decode_fn(params, caches, cur_len, tokens, placements=None):
            return decode_step(
                params, caches, cur_len, tokens, config, policy, placements
            )

        return decode_fn, ("caches",)
    raise ValueError(shape.kind)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             moe_backend: str | None = None) -> dict:
    import dataclasses

    config = get_config(arch)
    if moe_backend is not None and config.is_moe:
        # lower the cell with the selected MoE data plane — with "pallas"
        # the fused kernels trace per-shard inside shard_map on the
        # production mesh (the path PR 2 wired; einsum fallback is gone)
        config = dataclasses.replace(config, moe_backend=moe_backend)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if moe_backend is not None and config.is_moe:
        cell["moe_backend"] = moe_backend
    ok, why = shape_applicable(config, shape)
    if not ok:
        cell.update(status="skipped", reason=why)
        return cell
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        policy = policy_for(
            mesh, step_kind=shape.kind, global_batch=shape.global_batch,
            config=config,
        )
        kwargs, _ = input_specs(config, shape, policy)
        fn, donate = build_step_fn(config, shape, policy)
        with mesh:
            jitted = jax.jit(fn, donate_argnames=donate or None)
            lowered = jitted.lower(**kwargs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        coll = collective_stats(hlo)
        walk = compute_stats(hlo)
        xla_peak = mem.peak_memory_in_bytes
        mem_d = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            # resident = live arguments (params/caches) + XLA peak heap
            "peak_bytes": int(
                mem.argument_size_in_bytes
                - mem.alias_size_in_bytes
                + xla_peak
            ),
            "xla_peak_bytes": int(xla_peak),
        }
        cell.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory=mem_d,
            fits_16gb=mem_d["peak_bytes"] <= 16 * 1024**3,
            cost={
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                "transcendentals": float(cost.get("transcendentals", 0.0)),
            },
            # trip-aware structural walk (XLA cost_analysis undercounts
            # nested/transformed loop bodies — see hlo_analysis.compute_stats)
            hlo_walk={
                "flops": walk["flops"],
                "bytes": walk["bytes"],
                "unresolved_loops": walk["unresolved_loops"],
            },
            collectives=coll,
            hlo_bytes=len(hlo),
        )
    except Exception as e:  # a failing cell is a bug to fix, not to hide
        cell.update(
            status="error",
            error=f"{type(e).__name__}: {e}",
            traceback=traceback.format_exc()[-2000:],
        )
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--moe-backend", default=None,
                    choices=("einsum", "pallas", "dense_ref"),
                    help="MoE data-plane backend for MoE archs (default: "
                    "each config's own setting)")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args(argv)

    cells: list[tuple[str, str]] = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells.append((args.arch, args.shape))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    n_err = 0
    for arch, shape in cells:
        key = f"{arch}|{shape}|{'2x16x16' if args.multi_pod else '16x16'}"
        cell = run_cell(
            arch, shape, multi_pod=args.multi_pod,
            moe_backend=args.moe_backend,
        )
        results[key] = cell
        status = cell["status"]
        extra = ""
        if status == "ok":
            gb = cell["memory"]["peak_bytes"] / 1024**3
            extra = (
                f" compile={cell['compile_s']:.1f}s peak={gb:.2f}GB "
                f"fits={cell['fits_16gb']} "
                f"coll={cell['collectives']['total_bytes']/1e6:.1f}MB"
            )
        elif status == "error":
            n_err += 1
            extra = " " + cell["error"][:160]
        print(f"[{status:7s}] {key}{extra}", flush=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
