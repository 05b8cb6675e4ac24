"""Where a traced window's time goes, by the program's own spans and scopes.

    python3 bench/span_split.py --workload <name> --seconds <s>
        --seeds <n,n,...> [--record <dir> --record-seeds <n,n,...>]

For each seed, one run of the cell as ``run.py --trace 1`` makes it, in one
process. Beside the run's result it reduces what the trace holds of the
program (``program_trace.py``): the host time of the slice by the innermost
open span, benchmark's or program's, and the idle part of each; the idle
gaps labelled that way; the decode program's device time by named scope,
through the scope map of its optimized HLO, taken after the window; and the
readers ``control_ms_per_step``, ``control_idle_share`` and
``moe_dispatch_ms_per_step``. One JSON line per seed.

``--record`` then makes one more run, with a slice of ``RECORD_SLICE_S``
seconds and a shorter window, on each of ``--record-seeds`` in turn until
the slice holds a prefill, and writes its raw trace
(``granite-decode-spans.xplane.pb``) and the decode program's HLO text
(``granite-decode-spans.hlo.txt``) into the directory given: the recorded
trace of the reducer's tests.

It observes ``cell.run`` through wrappers around ``cell.Hooks.detach``,
``cell.end_to_end`` and ``xplane.reduce_dir``, and changes nothing that
the run computes or reports.
"""
from __future__ import annotations

import time

import argparse
import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as entry  # noqa: E402

RECORD_SLICE_S = 0.9
RECORD_MIX = {"trace_seconds": RECORD_SLICE_S, "ramp_seconds": 10.0}
RECORD_WINDOW_S = 20.0


class Observer:
    """What one run leaves behind for the program's reduction."""

    def __init__(self, cell, xplane, program_trace):
        self.hlo = self.e2e = self.program = self.trace = None
        self.record_dir = None
        detach, end_to_end = cell.Hooks.detach, cell.end_to_end
        reduce_dir = xplane.reduce_dir
        obs = self

        def detach_after_hlo(hooks):
            # ``eng._decode`` is the hook here; the jitted program is the
            # one it holds. A persistent-cache hit, not a compile.
            obs.hlo = hooks._decode.lower(
                *hooks.eng._decode_args()).compile().as_text()
            detach(hooks)

        def end_to_end_kept(*args, **kw):
            obs.e2e = end_to_end(*args, **kw)
            return obs.e2e

        def reduce_both(log_dir):
            obs.trace = reduce_dir(log_dir)
            obs.program = program_trace.reduce_dir(log_dir, obs.hlo)
            if obs.record_dir is not None:
                src = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
                dst = obs.record_dir / "granite-decode-spans"
                shutil.copy(src, dst.with_suffix(".xplane.pb"))
                dst.with_suffix(".hlo.txt").write_text(obs.hlo)
            return obs.trace

        cell.Hooks.detach = detach_after_hlo
        cell.end_to_end = end_to_end_kept
        xplane.reduce_dir = reduce_both

    def reset(self, record_dir=None):
        self.hlo = self.e2e = self.program = self.trace = None
        self.record_dir = record_dir


def summary(seed, result, obs, program_trace) -> dict:
    pt = program_trace
    red, prog = obs.trace, obs.program
    ctx = SimpleNamespace(trace=red, program=prog)
    by_scope, runs = pt.scope_time(red, prog.scopes)
    decode_s = sum(by_scope.values())
    steps = pt.decode_steps(prog)
    spans: dict[str, list] = {}
    for name, s, e, _ in prog.spans:
        n_t = spans.setdefault(name, [0, 0.0])
        n_t[0] += 1
        n_t[1] += (e - s) / 1e6
    split = pt.host_split(red, prog)
    idle_total = red.window_s - red.busy_s
    e2e = obs.e2e[0] if obs.e2e else {}
    return {
        "seed": seed, "correct": result["correct"],
        "setup_s": e2e.get("setup_s"), "itl_p50_ms": e2e.get("itl_p50_ms"),
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "control_ms_per_step": pt.control_ms_per_step(ctx),
        "control_idle_share": pt.control_idle_share(ctx),
        "moe_dispatch_ms_per_step": pt.moe_dispatch_ms_per_step(ctx),
        "window_s": red.window_s, "idle_s": idle_total,
        "decode_steps": steps, "decode_runs": runs,
        # per decode step: host wall and idle ms by innermost open span
        "split_ms_per_step": {k: [1e3 * v[0] / max(steps, 1),
                                  1e3 * v[1] / max(steps, 1)]
                              for k, v in split.items()},
        "bare_step_idle_share": (split.get("bench.step", [0, 0])[1]
                                 / idle_total if idle_total > 0 else None),
        "spans": {k: [n, t] for k, (n, t) in sorted(spans.items())},
        "idle_gaps": pt.merged(red, prog).idle_gaps(10),
        "scope_ms_per_run": {k: 1e3 * v / max(runs, 1)
                             for k, v in sorted(by_scope.items())},
        "joined_share": (1 - by_scope.get(pt.UNJOINED, 0.0) / decode_s
                         if decode_s > 0 else None),
        "unscoped_share": (by_scope.get(pt.UNSCOPED, 0.0) / decode_s
                           if decode_s > 0 else None),
        "top_ops": [[n, s, prog.scopes.get(n.lstrip("%"), pt.UNJOINED)]
                    for n, s in red.top_ops(10)],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--record", type=Path)
    ap.add_argument("--record-seeds", default="1,2,3")
    args = ap.parse_args(argv)
    import jax

    entry.use_cache(jax)
    import cell
    import program_trace
    import readers
    import xplane

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("span_split: JAX found no TPU; nothing run", file=sys.stderr)
        return 1
    obs = Observer(cell, xplane, program_trace)
    log = lambda line: print(line, file=sys.stderr)  # noqa: E731
    for seed in (int(s) for s in args.seeds.split(",")):
        obs.reset()
        r = cell.run(args.workload, seed, args.seconds, True,
                     t_start=time.perf_counter(), device=dev, log=log)
        print(json.dumps(summary(seed, r, obs, program_trace)), flush=True)
    if args.record is not None:
        args.record.mkdir(parents=True, exist_ok=True)
        for seed in (int(s) for s in args.record_seeds.split(",")):
            obs.reset(record_dir=args.record)
            r = cell.run(args.workload, seed, RECORD_WINDOW_S, True,
                         t_start=time.perf_counter(), device=dev, log=log,
                         mix_override=RECORD_MIX)
            row = summary(seed, r, obs, program_trace)
            row["prefills"] = obs.trace.module_time(readers.PREFILL_MODULE)[0]
            row["recorded"] = str(args.record)
            print(json.dumps(row), flush=True)
            if row["prefills"]:
                break
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
