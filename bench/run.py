"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration from the seed (weights on the chip in their
served dtype), warms every shape its traffic uses, offers the traffic for
``--seconds`` seconds of wall clock, then checks a sample of the served
tokens against the plain float32 reference. The last line of standard
output is one JSON object: the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``. The numbers compared for
``correct`` are printed beside their limits as the last lines of standard
error and under ``checks`` in that object.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))


def use_cache(jax) -> None:
    """Keep every compiled program in JAX's persistent cache, where the
    program puts it: ``JAX_COMPILATION_CACHE_DIR`` when that is set, else
    a fixed directory inside the checkout. Small and quick programs too."""
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    use_cache(jax)
    import cell

    bench = cell.load_benchmark()
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}.get(args.workload)
    if chips is None:
        print(f"bench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s); nothing run",
              file=sys.stderr)
        return 1
    result = cell.run(args.workload, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, device=devices[0])
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    raise SystemExit(main())
