"""Knee sweep of an open-loop cell, on the chip.

    python3 bench/sweep.py --workload <name> --seconds <s> --rates <r,r,...>

Runs the cell's traffic at each offered rate (requests per second) in one
process and prints what each rate sustained: output tokens per second,
the TTFT and ITL tails, and the requests still unfinished or never started
at the window's close. The knee is the highest rate whose backlog does not
grow; a cell's traffic file then fixes its rate once, below the knee.
"""
from __future__ import annotations

import time

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as entry  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax

    entry.use_cache(jax)
    import cell

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("sweep: JAX found no TPU; nothing run", file=sys.stderr)
        return 1
    for rate in (float(r) for r in args.rates.split(",")):
        t0 = time.perf_counter()
        r = cell.run(args.workload, args.seed, args.seconds, False, t_start=t0,
                     device=dev, mix_override={"rate_rps": rate},
                     log=lambda line: print(line, file=sys.stderr))
        print(json.dumps({"rate_rps": rate, "attempted": r["attempted"],
                          "correct": r["correct"],
                          "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                          "backlog": r.get("backlog")}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
