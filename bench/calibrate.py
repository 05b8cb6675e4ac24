"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seconds <s> --seeds <n,n,...>

For each seed, one run of the cell as ``run.py`` makes it (at the cell's
own size and load, with a window of ``--seconds``), then the numbers the
correctness check can compare (``reference.gap_stats``) for the served
tokens under the float32 reference and for the tokens the float8 control
puts first on the same sample, each judged by the rule that decides
``correct``. All seeds run in one process. One JSON line per seed, then a
summary line: how many seeds each side passed, and the program's largest
reading and the control's smallest, for each number.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as entry  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args(argv)
    import jax

    entry.use_cache(jax)
    import cell

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("calibrate: JAX found no TPU; nothing run", file=sys.stderr)
        return 1
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = cell.run(args.workload, seed, args.seconds, False, t_start=t0,
                     device=dev, control=not args.no_control)
        row = {"seed": seed, "correct": r["correct"],
               "control_correct": r.get("control_correct"),
               "program": r["stats"], "control": r.get("control"),
               "tokens_checked": r["checks"]["tokens_checked"]["value"],
               "dropped": r["checks"]["dropped_tokens"]["value"],
               "metrics": {k: v["value"] for k, v in r["metrics"].items()},
               "memory_peak_bytes": r["device"]["memory_peak_bytes"],
               "run_s": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows),
               "program_correct": sum(r["correct"] for r in rows),
               "control_correct": sum(bool(r["control_correct"]) for r in rows)}
    for name in rows[0]["program"]:
        summary[name] = {
            "program_max": max(r["program"][name] for r in rows),
            "control_min": min((r["control"][name] for r in rows
                                if r["control"]), default=None)}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
