"""The serving engine's wall-clock program spans, read back from a profiler
trace of the tiny configuration served on the CPU, and the reduction of
program spans and HLO scopes (``program_trace.py``) on small inputs."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest
import program_trace as pt
import xplane
from conftest import DATA

STEPS = 20  # past GEM's 16-step warm-up window: a replan and a migration


def _serve(steps):
    """The tiny configuration's engine, four requests submitted, ``steps``
    steps served; the seeds make the warm-up plan move experts."""
    import cell
    from repro.launch.serve import build_engine, init_placed_params
    from repro.sharding import host_policy

    cfg = json.loads((DATA / "tiny-moe.json").read_text())
    model, ecfg = cell.program(cfg)
    params = init_placed_params(model, host_policy(), 3)
    eng = build_engine(model, host_policy(), ecfg, params=params,
                       variability="high", num_devices=4, seed=2)
    rng = np.random.default_rng(2)
    for n in (8, 16, 8, 12):
        eng.submit(rng.integers(0, model.vocab_size, n), 20)
    for _ in range(steps):
        eng.step()
    return eng


def _host_spans(log_dir):
    from jax.profiler import ProfileData

    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(pt.PROGRAM_PREFIXES):
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats), line.name))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    plain = _serve(STEPS)
    log_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(log_dir)):
        eng = _serve(STEPS)
    return plain, eng, _host_spans(log_dir)


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_each_engine_span_nests_in_its_step(traced):
    _, eng, spans = traced
    steps = [sp for sp in spans if sp[0] == "engine.step"]
    assert len(steps) == STEPS
    assert [sp[3]["step"] for sp in steps] == list(range(STEPS))
    assert all(sp[3]["step_num"] == sp[3]["step"] and "active" in sp[3]
               for sp in steps)
    names = {sp[0] for sp in spans}
    assert {"engine.admit", "engine.prefill", "engine.dispatch", "engine.sync",
            "engine.attribution", "engine.regret", "engine.controller",
            "engine.migrate", "engine.finish"} <= names
    for sp in spans:
        if sp[0] == "engine.step" or not sp[0].startswith("engine."):
            continue
        outer = [st for st in steps if _inside(sp, st)]
        assert len(outer) == 1, sp
        assert sp[3]["step"] == outer[0][3]["step"], sp
    prefills = [sp for sp in spans if sp[0] == "engine.prefill"]
    assert sorted(sp[3]["uid"] for sp in prefills) == [1, 2, 3, 4]
    assert sorted(sp[3]["tokens"] for sp in prefills) == [8, 8, 12, 16]
    migrates = [sp for sp in spans if sp[0] == "engine.migrate"]
    assert [sp[3]["moves"] for sp in migrates] == [
        r["moves"] for r in eng.migration_records]
    assert all(sp[4] == spans[0][4] for sp in spans)  # one host thread


def test_controller_spans_sit_inside_engine_controller(traced):
    _, eng, spans = traced
    ctrl = [sp for sp in spans if sp[0] == "engine.controller"]
    inner = [sp for sp in spans if sp[0].startswith("controller.")]
    assert {sp[0] for sp in inner} == {"controller.drift", "controller.replan"}
    for sp in inner:
        assert sum(_inside(sp, c) for c in ctrl) == 1, sp
    replans = [sp for sp in inner if sp[0] == "controller.replan"]
    assert [(sp[3]["step"], sp[3]["reason"]) for sp in replans] == [
        (r["step"], r["reason"]) for r in eng.controller.replans]


def test_spans_leave_tokens_and_events_unchanged(traced):
    plain, eng, _ = traced
    assert [r.generated for r in eng.finished] == [
        r.generated for r in plain.finished]
    assert len(eng.finished) == 4
    # the simulated clock's events stay apart from the program spans
    names = {ev["name"] for ev in eng.telemetry.events}
    assert not any(n.startswith(pt.PROGRAM_PREFIXES) for n in names)


HLO = """\
HloModule jit__decode_paged
%fused_computation (param_0: bf16[4]) -> bf16[4] {
  %param_0 = bf16[4]{0} parameter(0)
  ROOT %neg.1 = bf16[4]{0} negate(%param_0), metadata={op_name="jit(_decode_paged)/layer_scan/while/body/closed_call/ffn/route/neg"}
}
ENTRY %main {
  %copy.97 = bf16[4]{0} copy(%p), metadata={op_name="jit(_decode_paged)/layer_scan/while/body/dynamic_slice" stack_frame_id=11}
  %fusion.1 = bf16[4]{0} fusion(%copy.97), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(_decode_paged)/layer_scan/while/body/closed_call/ffn/route/neg"}
  %fusion.2 = bf16[4]{0} fusion(%copy.97), kind=kLoop, metadata={op_name="jit(_decode_paged)/layer_scan/while/body/closed_call/ffn/mul"}
  %moe_ffn_pallas.12 = f32[4]{0} custom-call(%fusion.1), metadata={op_name="jit(_decode_paged)/layer_scan/while/body/closed_call/ffn/expert_compute/jit(moe_ffn_pallas)/pallas_call"}
  %fusion.3 = bf16[4]{0} fusion(%moe_ffn_pallas.12), metadata={op_name="jit(_decode_paged)/lm_head/dot_general"}
  %gte.4 = bf16[4]{0} get-tuple-element(%t), index=0
  ROOT %while.5 = bf16[4]{0} while(%gte.4), metadata={op_name="jit(_decode_paged)/layer_scan/while"}
}
"""


def test_scope_map_takes_the_innermost_scope():
    scopes = pt.scope_map(HLO)
    assert scopes["copy.97"] == "layer_scan"
    assert scopes["fusion.1"] == scopes["neg.1"] == "route"
    assert scopes["fusion.2"] == "ffn"
    assert scopes["moe_ffn_pallas.12"] == "expert_compute"
    assert scopes["fusion.3"] == "lm_head"
    assert scopes["gte.4"] == scopes["param_0"] == pt.UNSCOPED
    assert pt.scope_of("jit(f)/while/body/dynamic_slice") == pt.UNSCOPED


def _reduction(ops, gaps, spans, window_ns):
    return xplane.Reduction(
        window_s=window_ns / 1e9,
        busy_s=(window_ns - sum(e - s for s, e in gaps)) / 1e9, devices=1,
        modules=[("jit__decode_paged(1)", 100.0, 400.0)], ops=ops,
        spans=spans, gaps=gaps)


def test_scope_time_and_readers_on_a_small_reduction():
    ops = [("%copy.97 = bf16[4]", 100.0, 150.0),
           ("%fusion.1 = bf16[4]", 150.0, 170.0),
           ("%while.5 = bf16[4] while()", 100.0, 390.0),  # encloses the rest
           ("%moe_ffn_pallas.12 = f32[4]", 170.0, 300.0),
           ("%fusion.2 = bf16[4]", 300.0, 310.0),
           ("%gte.4 = bf16[4]", 310.0, 320.0),
           ("%other.9 = bf16[4]", 320.0, 330.0),
           ("%fusion.3 = bf16[4]", 330.0, 400.0),
           ("%fusion.1 = bf16[4]", 500.0, 510.0)]  # outside the module run
    gaps = [(0.0, 100.0), (400.0, 500.0), (510.0, 1000.0)]
    red = _reduction(ops, gaps, [("bench.step", 0.0, 1000.0)], 1000.0)
    prog = pt.ProgramTrace(
        window=(0.0, 1000.0),
        spans=[("engine.step", 10.0, 990.0, {"step": 0}),
               ("engine.dispatch", 20.0, 90.0, {"step": 0}),
               ("engine.sync", 100.0, 400.0, {"step": 0}),
               ("engine.regret", 600.0, 700.0, {"step": 0}),
               ("engine.controller", 700.0, 900.0, {"step": 0}),
               ("controller.drift", 750.0, 800.0, {"step": 0})],
        scopes=pt.scope_map(HLO))
    by_scope, runs = pt.scope_time(red, prog.scopes)
    assert runs == 1
    assert by_scope == pytest.approx({
        "layer_scan": 50e-9, "route": 20e-9, "expert_compute": 130e-9,
        "ffn": 10e-9, pt.UNSCOPED: 10e-9, pt.UNJOINED: 10e-9,
        "lm_head": 70e-9})
    ctx = type("Ctx", (), {"trace": red, "program": prog})
    assert pt.moe_dispatch_ms_per_step(ctx) == pytest.approx(20e-6)
    # regret 100 ns and controller 200 ns (drift inside it) over one step
    assert pt.control_ms_per_step(ctx) == pytest.approx(300e-6)
    assert pt.control_idle_share(ctx) == pytest.approx(100 * 300 / 1000)
    split = pt.host_split(red, prog)
    assert split["controller.drift"] == pytest.approx([50e-9, 50e-9])
    assert split["engine.sync"] == pytest.approx([300e-9, 0.0])
    # idle with no inner span open: 10 + 10 + (100 + 90) + 90 ns
    assert split["engine.step"][1] == pytest.approx(300e-9)
    assert split["bench.step"] == pytest.approx([20e-9, 20e-9])
    # each gap goes whole to the span open at its middle
    gaps = pt.merged(red, prog).idle_gaps(4)
    assert dict(gaps[:3]) == pytest.approx({
        "controller.drift": 490e-9, "engine.dispatch": 100e-9,
        "engine.step": 100e-9})
    assert gaps[3] == ["longest:controller.drift", pytest.approx(490e-9)]
    # the parent's context has no ``program``: the readers read nothing
    bare = type("Ctx", (), {"trace": red})
    assert pt.control_ms_per_step(bare) is None
    assert pt.moe_dispatch_ms_per_step(bare) is None


# -- a trace recorded on a TPU v5e chip -----------------------------------
# ``span_split.py --record``: granite-moe-3b-a800m in the chat cell, four
# decode steps and two prefills inside ``bench.slice``, with the engine's
# program spans, and the decode program's optimized HLO text from the
# same run (its source file names made relative to the checkout).


@pytest.fixture(scope="module")
def recorded():
    import gzip

    from jax.profiler import ProfileData

    raw = (DATA / "granite-decode-spans.xplane.pb.gz").read_bytes()
    profile = ProfileData.from_serialized_xspace(gzip.decompress(raw))
    hlo = gzip.decompress(
        (DATA / "granite-decode-spans.hlo.txt.gz").read_bytes()).decode()
    red = xplane.reduce_profile(profile)
    return red, pt.reduce_program(profile, hlo)


def test_recorded_slice_holds_every_step_span(recorded):
    red, prog = recorded
    assert red.window_s == pytest.approx(1.370653066)
    assert red.busy_s == pytest.approx(0.950323692)
    assert red.module_time(r"_decode_paged")[0] == pt.decode_steps(prog) == 4
    assert red.module_time(r"_prefill_fn")[0] == 2
    names = {n for n, *_ in prog.spans}
    assert names == {"engine.step", "engine.admit", "engine.prefill",
                     "engine.dispatch", "engine.sync", "engine.attribution",
                     "engine.regret", "engine.controller", "engine.finish",
                     "controller.drift"}
    steps = {sp[3]["step"] for sp in prog.spans if sp[0] == "engine.step"}
    assert all(sp[3]["step"] in steps for sp in prog.spans)


def test_recorded_idle_is_named_by_program_spans(recorded):
    red, prog = recorded
    # the benchmark's own labels file every idle gap under ``bench.step``
    assert red.idle_gaps(1) == [["bench.step", pytest.approx(0.420329374)]]
    gaps = pt.merged(red, prog).idle_gaps(6)
    assert gaps[:4] == [["engine.regret", pytest.approx(0.412119092)],
                        ["engine.dispatch", pytest.approx(0.006501723)],
                        ["engine.prefill", pytest.approx(0.001681562)],
                        ["engine.sync", pytest.approx(0.000026997)]]
    assert gaps[4] == ["longest:engine.regret", pytest.approx(0.106537722)]
    split = pt.host_split(red, prog)
    idle = red.window_s - red.busy_s
    assert sum(v[1] for v in split.values()) == pytest.approx(idle)
    assert sum(v[0] for v in split.values()) == pytest.approx(red.window_s)
    assert split["engine.regret"] == pytest.approx([0.393581094] * 2)
    assert split["engine.sync"] == pytest.approx([0.441026049, 0.009836027])
    # host time in step() outside every program span: under 10 % of idle
    assert split["bench.step"][1] / idle == pytest.approx(2.113e-4, rel=1e-3)


def test_recorded_readers(recorded):
    import readers

    red, prog = recorded
    ctx = type("Ctx", (), {"trace": red, "program": prog})
    assert pt.control_ms_per_step(ctx) == pytest.approx(99.0834535)
    assert pt.control_idle_share(ctx) == pytest.approx(28.915691639)
    assert pt.moe_dispatch_ms_per_step(ctx) == pytest.approx(3.04515075)
    # the accepted readers read this trace as before
    assert readers.decode_step_ms(ctx) == pytest.approx(107.7832685)
    assert readers.device_idle_share(ctx) == pytest.approx(30.666357843)


def test_recorded_scope_map_covers_the_decode_program(recorded):
    red, prog = recorded
    by_scope, runs = pt.scope_time(red, prog.scopes)
    total = sum(by_scope.values())
    assert runs == 4
    assert total * 1e3 / runs == pytest.approx(107.74224, rel=1e-6)
    assert pt.UNJOINED not in by_scope  # every operation found by name
    assert by_scope[pt.UNSCOPED] / total == pytest.approx(0.136230349)
    assert by_scope["layer_scan"] / total == pytest.approx(0.581540017)
    # the copies and slices out of the scan's stacked operands
    for name in ("copy.97", "copy.98", "copy.111", "copy.113",
                 "dynamic-slice_bitcast_fusion.6",
                 "dynamic-slice_bitcast_fusion.7",
                 "dynamic-slice_bitcast_fusion.8"):
        assert prog.scopes[name] == "layer_scan"
    assert prog.scopes["moe_ffn_pallas.12"] == "expert_compute"
    assert prog.scopes["fusion.175"] == "build_dispatch"
