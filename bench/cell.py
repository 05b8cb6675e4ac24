"""One run of one benchmark cell: set-up, the measured open-loop window,
and the correctness check against the plain reference.

The system under test is the program's serving engine, built by its own
entry (``repro.launch.serve.build_engine``) and driven through
``ServingEngine.submit`` and ``ServingEngine.step``. Everything that
belongs to one configuration, traffic mix or per-layer metric is read from
its own file, found by the name ``BENCHMARK.json`` gives it.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

import reference  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_files(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration file, traffic file) of cell ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    return cell, cfg, traffic.load(cell["traffic"])


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def program(cfg: dict):
    """The program's ModelConfig and EngineConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    from repro.core import GEMConfig
    from repro.online import MigrationConfig
    from repro.serving import EngineConfig
    from repro.serving.kv_cache import PagedKVConfig

    prog, eng = cfg["program"], cfg["engine"]
    model = ModelConfig(
        name=cfg["name"], family=prog["family"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        num_experts=cfg["num_local_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_d_ff=cfg["intermediate_size"], expert_tp=prog["expert_tp"],
        sliding_window=cfg["sliding_window"] or 0,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=prog["dtype"],
        moe_backend=prog["moe_backend"],
        pallas_block_c=prog["pallas_block_c"],
        pallas_block_f=prog["pallas_block_f"],
        capacity_factor=prog["capacity_factor"],
        decode_capacity_factor=prog["decode_capacity_factor"],
    )
    engine = EngineConfig(
        max_batch=eng["max_batch"], max_len=eng["max_len"],
        gem=GEMConfig(), placement_policy=eng["placement_policy"],
        moe_backend=prog["moe_backend"], decode_mode=eng["decode_mode"],
        kv_mode=eng["kv_mode"], kv=PagedKVConfig(block_size=eng["kv_block_size"]),
        online=eng["online"],
        migration=MigrationConfig(max_moves_per_step=eng["max_moves_per_step"]),
    )
    return model, engine


@dataclasses.dataclass
class Request:
    due: float  # absolute host time it was due
    prompt: np.ndarray
    submitted: float = 0.0
    times: list = dataclasses.field(default_factory=list)  # token deliveries
    tokens: list = dataclasses.field(default_factory=list)  # as served
    done: bool = False


@dataclasses.dataclass
class LayerContext:
    """What the per-layer readers (``readers.py``) read."""
    dims: reference.Dims
    peak: dict
    trace: object = None  # trace.Reduction of the traced slice
    slice_counts: list = dataclasses.field(default_factory=list)
    slice_prefill_tokens: int = 0
    step_s: float = 0.0
    step_flops: float = 0.0


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.count += 1

    def _event(self, event, **kw):
        if self.on and event == CACHE_HIT_EVENT:
            self.count += 1


class Hooks:
    """Observes the engine without changing what it computes: the token
    each prefill fed to decode, and, inside the window, the useful FLOPs
    of each prefill and decode and the expert counts of each decode."""

    def __init__(self, eng, ctx: LayerContext):
        self.eng, self.ctx = eng, ctx
        self.first_fed: dict[int, int] = {}
        self.in_window = self.in_slice = False
        self._install = eng._install_paged_slot
        self._decode = eng._decode
        eng._install_paged_slot = self.install
        eng._decode = self.decode

    def install(self, slot, req):
        self._install(slot, req)
        self.first_fed[req.uid] = int(self.eng.last_token[slot])
        if self.in_window:
            self.ctx.step_flops += work.prefill_flops(self.ctx.dims, req.prompt_len)
        if self.in_slice:
            self.ctx.slice_prefill_tokens += req.prompt_len

    def decode(self, *args):
        eng = self.eng
        if self.in_window:
            ctx_lens = eng.cur_len[eng.installed] + 1
            self.ctx.step_flops += sum(
                work.decode_flops(self.ctx.dims, int(c)) for c in ctx_lens)
        out = self._decode(*args)
        if self.in_slice:
            self.ctx.slice_counts.append(out[2].expert_counts)
        return out

    def detach(self):
        del self.eng._install_paged_slot, self.eng._decode
        self.eng = None


def warm_up(eng, arrivals, max_rounds: int = 6) -> int:
    """Serve the set-up requests until GEM's warm-up placement has been
    applied; returns the rounds served. Every prompt length, the decode
    program and the migration program are compiled here."""
    rounds = 0
    while rounds < max_rounds:
        for a in arrivals:
            eng.submit(a.prompt, a.max_new_tokens, task=a.topic)
        while eng.scheduler.has_work():
            eng.step()
        rounds += 1
        ctrl = eng.controller
        if eng.placement_applied and (ctrl is None or not ctrl.migrating):
            break
    if eng.jit_trace_counts["migrate"] == 0:
        # the warm-up plan moved nothing: compile the migration program on
        # an identity row map, which leaves weights and tables as they are
        L, S = eng.params["blocks"]["moe"]["w_gate"].shape[:2]
        src = np.tile(np.arange(S, dtype=np.int32), (L, 1))
        eng._apply_migration_sources(src, swap_tables=True)
    return rounds


def _collect(eng, recs, seen_finished: int, now: float) -> int:
    """Stamp every token delivered since the last call with ``now``."""
    live = list(eng.scheduler.active.values()) + eng.finished[seen_finished:]
    for req in live:
        rec = recs.get(req.uid)
        if rec is not None:
            while len(rec.times) < len(req.generated):
                rec.times.append(now)
    return len(eng.finished)


def drive(eng, hooks: Hooks, arrivals, seconds: float, *, ramp: float = 0.0,
          trace_slice=None):
    """The open loop: submit each request when it falls due, step the
    engine whenever it has work, sleep until the next due time when it
    has none. The window opens ``ramp`` seconds after the loop starts.
    Returns (records by uid, window start, window end, profiler log
    directory or None)."""
    import jax

    Annotation = jax.profiler.TraceAnnotation
    recs: dict[int, Request] = {}
    ctx = hooks.ctx
    seen = len(eng.finished)
    t0 = time.perf_counter() + ramp
    end = t0 + seconds
    i, n = 0, len(arrivals)
    log_dir, slice_span = None, None
    trace_at = None if trace_slice is None else (t0 + trace_slice[0],
                                                 t0 + trace_slice[1])
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        hooks.in_window = now >= t0
        if trace_at is not None:
            if log_dir is None and now >= trace_at[0]:
                log_dir = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(
                    log_dir, profiler_options=_profiler_options())
                slice_span = Annotation("bench.slice")
                slice_span.__enter__()
                hooks.in_slice = True
                # starting the profiler takes seconds: the slice keeps its
                # length from the moment the trace is live
                trace_at = (trace_at[0], time.perf_counter()
                            + trace_slice[1] - trace_slice[0])
            elif slice_span is not None and now >= trace_at[1]:
                _stop_slice(hooks, slice_span)
                slice_span, trace_at = None, None
        while i < n and t0 + arrivals[i].due <= now:
            a = arrivals[i]
            with Annotation("bench.submit"):
                uid = eng.submit(a.prompt, a.max_new_tokens, task=a.topic)
            recs[uid] = Request(t0 + a.due, a.prompt, submitted=time.perf_counter())
            i += 1
        if eng.scheduler.has_work():
            s = time.perf_counter()
            with Annotation("bench.step"):
                eng.step()
            now = time.perf_counter()
            if hooks.in_window:
                ctx.step_s += now - s
            seen = _collect(eng, recs, seen, now)
        else:
            nxt = t0 + arrivals[i].due if i < n else end
            with Annotation("bench.wait"):
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
    hooks.in_window = False
    if slice_span is not None:
        _stop_slice(hooks, slice_span)
    return recs, t0, end, log_dir


def _profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _stop_slice(hooks, span):
    import jax

    hooks.in_slice = False
    span.__exit__(None, None, None)
    jax.profiler.stop_trace()


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(recs, t0: float, end: float, setup_s: float) -> dict:
    """The end-to-end metrics of the window, by the host's clock, and the
    tails beside them (``tails``, for the log). Time to first token and
    request latency are over the requests due in the window; tokens and
    inter-token gaps are every request's, delivered inside it."""
    ttft, e2e, itl, tokens = [], [], [], 0
    for r in recs.values():
        times = [t for t in r.times if t0 <= t <= end]
        tokens += len(times)
        itl.extend(np.diff(times).tolist())
        if r.due < t0:
            continue
        ttft.append((times[0] if times else end) - r.due)
        finished = r.done and len(times) == len(r.tokens)
        e2e.append((times[-1] if finished else end) - r.due)
    out = {"output_tokens_per_s": tokens / (end - t0), "setup_s": setup_s}
    tails = {}
    for name, xs in (("ttft", ttft), ("e2e", e2e), ("itl", itl)):
        for q in (50, 75, 90, 95, 99):
            if xs:
                tails[f"{name}_p{q}_ms"] = 1e3 * percentile(xs, q)
    out.update(tails)
    return out, {"ttft": len(ttft), "itl": len(itl)}


def check_sample(recs, first_fed, seed: int, count: int):
    """A sample drawn from the seed of the finished window requests, the
    longest of them always in it: ``[(prompt, served tokens)]``, where the
    served tokens are the one each prefill fed to decode followed by every
    token decoded."""
    done = sorted(uid for uid, r in recs.items() if r.done)
    if not done:
        return []
    longest = max(done, key=lambda u: (len(recs[u].prompt) + len(recs[u].tokens), u))
    rest = [u for u in done if u != longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 4])
    pick = [longest] + [rest[j] for j in rng.permutation(len(rest))[: count - 1]]
    return [(recs[u].prompt, [first_fed[u]] + list(recs[u].tokens)) for u in pick]


def judge(stats: dict, limits: dict, dropped, sample) -> bool:
    """``correct``: some finished request was checked, no token was
    dropped, and every number compared is within its limit."""
    return bool(sample and dropped == 0
                and all(n in stats and stats[n] <= limits[n] for n in limits))


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, device, breaker=None, control: bool = False,
        mix_override=None, log=print) -> dict:
    """One run of one cell; returns the result object.

    ``breaker``, for tests, is called with the built engine before the
    window and may plant a fault in it. ``control`` also reads the float8
    control's gap statistics on the same sample and judges them as the
    program's are (``control``, ``control_correct``; ``calibrate.py``);
    ``mix_override`` replaces keys of the traffic file (``sweep.py``).
    """
    import jax

    from repro.launch.serve import build_engine, init_placed_params
    from repro.sharding import host_policy

    bench = load_benchmark()
    cell, cfg, mix = cell_files(bench, cell_name)
    mix = {**mix, **(mix_override or {})}
    model, ecfg = program(cfg)
    dims = reference.dims(cfg)
    ctx = LayerContext(dims=dims, peak=work.peaks(device.device_kind))
    compiles = CompileCounter()

    phases = {"start": time.perf_counter() - t_start}
    t = time.perf_counter()
    params = init_placed_params(model, host_policy(), int(seed) % 2**32)
    jax.block_until_ready(params)
    phases["weights"] = time.perf_counter() - t
    t = time.perf_counter()
    eng = build_engine(model, host_policy(), ecfg, params=params,
                       variability=cfg["engine"]["gem_variability"],
                       num_devices=cfg["engine"]["gem_devices"])
    del params
    phases["engine"] = time.perf_counter() - t
    t = time.perf_counter()
    hooks = Hooks(eng, ctx)
    rounds = warm_up(eng, traffic.warmup_arrivals(
        mix, model.vocab_size, seed, mix["warmup_requests"]))
    phases["warm_up"] = time.perf_counter() - t
    arrivals = traffic.window_arrivals(mix, model.vocab_size, seed, seconds)
    if breaker is not None:
        breaker(eng)
    jit_before = dict(eng.jit_trace_counts)
    reg = eng.telemetry.registry
    dropped_before = reg.counter("dispatch.dropped_tokens").value
    replans_before = len(eng.controller.replans) if eng.controller else 0
    migrations_before = len(eng.migration_records)
    ramp = float(mix.get("ramp_seconds", 0.0))
    trace_slice = None
    if trace:
        length = min(mix["trace_seconds"], seconds / 2)
        trace_slice = (seconds / 2 - length / 2, seconds / 2 + length / 2)

    compiles.on = True
    t_loop = time.perf_counter()
    recs, t0, end, log_dir = drive(eng, hooks, arrivals, seconds, ramp=ramp,
                                   trace_slice=trace_slice)
    compiles.on = False
    # set-up ends where the traffic starts; the ramp serves requests
    setup_s = t_loop - t_start

    live = {r.uid: r for r in list(eng.scheduler.active.values()) + eng.finished}
    for uid, rec in recs.items():
        req = live.get(uid)  # None: still queued, never admitted
        if req is not None:
            rec.tokens, rec.done = list(req.generated), req.done
    jit_after = eng.jit_trace_counts
    dropped = reg.counter("dispatch.dropped_tokens").value - dropped_before
    replans = (eng.controller.replans[replans_before:] if eng.controller else [])
    migrations = len(eng.migration_records) - migrations_before
    lateness = [r.submitted - r.due for r in recs.values()]
    due = {u: r for u, r in recs.items() if r.due >= t0}
    memory_peak = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))
    first_fed = dict(hooks.first_fed)
    hooks.detach()
    del eng, hooks, live
    gc.collect()

    tag = f"[{device.device_kind} x{len(jax.devices())}]"
    log(f"{tag} cell {cell_name} seed {seed}: setup {setup_s:.3f} s ("
        + ", ".join(f"{k} {v:.3f} s" for k, v in phases.items())
        + f"; {rounds} warm-up rounds), ramp {t0 - t_loop:.3f} s, "
        f"window {end - t0:.3f} s, "
        f"{len(due)} requests due in it, {len(recs) - len(due)} in the ramp")
    log(f"{tag} generator lateness: median {1e3 * percentile(lateness, 50):.3f} ms, "
        f"max {1e3 * max(lateness):.3f} ms" if lateness else
        f"{tag} generator lateness: no request due")
    traces_in_window = sum(jit_after[k] - jit_before[k] for k in jit_after)
    log(f"{tag} compiles in window: {compiles.count} (jit traces "
        f"{traces_in_window}); dropped tokens in window: {int(dropped)}")
    log(f"{tag} GEM in window: {len(replans)} replans "
        f"({', '.join(str(r['reason']) for r in replans) or 'none'}), "
        f"{migrations} migration applies")

    e2e, samples = end_to_end(recs, t0, end, setup_s)
    log(f"{tag} tails over {samples['ttft']} requests and {samples['itl']} "
        "gaps: " + ", ".join(f"{k} {v:.1f}" for k, v in e2e.items()
                             if k.endswith("_ms")))
    result = {"correct": False, "attempted": len(due), "failed": 0,
              "metrics": {}, "device": {
                  "platform": device.platform, "kind": device.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": memory_peak}}
    if trace and log_dir is not None:
        import xplane

        ctx.trace = xplane.reduce_dir(log_dir)
        _remove(log_dir)
        result["device"]["busy_s"] = ctx.trace.busy_s
        result["device"]["window_s"] = ctx.trace.window_s
        result["breakdown"] = {"device_ops": ctx.trace.top_ops(10),
                               "idle_gaps": ctx.trace.idle_gaps(10)}
    if trace:
        for m in bench["per_layer"]:
            if applies(m, cell_name):
                value = load_reader(m["name"])(ctx)
                if value is not None:
                    result["metrics"][m["name"]] = {"value": float(value),
                                                    "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if applies(m, cell_name) and m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}

    t = time.perf_counter()
    sample = check_sample(recs, first_fed, seed, mix["check_requests"])
    weights = reference.init_weights(dims, int(seed) % 2**32)
    gaps = reference.position_gaps(weights, dims, sample, ecfg.max_len)
    stats = reference.gap_stats(gaps)
    limits = cfg["check"]
    if control:
        result["control"] = reference.gap_stats(reference.position_gaps(
            weights, dims, sample, ecfg.max_len, control=True))
        result["control_correct"] = judge(result["control"], limits,
                                          dropped, sample)
    del weights
    log(f"{tag} reference check of {len(sample)} requests: "
        f"{time.perf_counter() - t:.3f} s; "
        + ", ".join(f"{k} {v:.4f}" for k, v in stats.items())
        + "; widest gap per request "
        + ", ".join(f"{len(p)}+{len(sv)}:{g.max():.3f}"
                    for (p, sv), g in zip(sample, gaps)))
    # no finished request to judge: the number is missing, not passing
    checks = {name: {"value": stats.get(name), "limit": limit}
              for name, limit in limits.items()}
    checks["dropped_tokens"] = {"value": int(dropped), "limit": 0}
    checks["tokens_checked"] = {
        "value": int(sum(len(s) for _, s in sample)), "limit": 1}
    result["backlog"] = {
        "unfinished": sum(not r.done for r in due.values()),
        "never_started": sum(not r.times for r in due.values())}
    result["correct"] = judge(stats, limits, dropped, sample)
    result["stats"] = stats
    result["checks"] = checks
    return result


def _remove(path):
    import shutil

    shutil.rmtree(path, ignore_errors=True)
