"""The program's own spans and scopes in a profiler trace, beside the
benchmark's reduction (``xplane.Reduction``).

The serving engine opens wall-clock program spans (``engine.*`` and
``controller.*``, ``jax.profiler.TraceAnnotation``) on the same host plane
as the benchmark's ``bench.*`` spans; their keyword args (``step``,
``uid``, ...) are event stats. The decode and prefill programs carry
``jax.named_scope`` names in the ``op_name`` metadata of their optimized
HLO, which the trace does not: the trace names an operation by its HLO
instruction only (``%copy.97 = ...``), so a scope map joins the two by
that name.

This reduces what the trace holds of both:

- the program spans in the traced slice, with their args;
- the host timeline split by the innermost open span, benchmark's or
  program's, and the device's idle gaps labelled by it;
- the decode program's device time by scope.

The readers at the end take the run's layer context with ``trace`` (the
``xplane.Reduction``) and ``program`` (a :class:`ProgramTrace`), and return
``None`` where either is missing.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import numpy as np

import xplane
from readers import DECODE_MODULE

PROGRAM_PREFIXES = ("engine.", "controller.")
# the GEM control plane's host work, nested spans included
CONTROL = ("engine.attribution", "engine.regret", "engine.controller",
           "controller.drift", "controller.replan", "engine.migrate",
           "engine.replan")
# named scopes of the compiled programs (models/model.py, models/dispatch.py);
# an operation belongs to the innermost one in its op_name path
SCOPES = ("layer_scan", "attention", "ffn", "route", "build_dispatch",
          "expert_compute", "combine", "lm_head")
MOE_DISPATCH = ("route", "build_dispatch", "combine")
UNSCOPED = "unscoped"
UNJOINED = "unjoined"

_INSTR = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = .*?op_name="([^"]*)"', re.M)
_NAMED = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = ', re.M)


@dataclasses.dataclass
class ProgramTrace:
    window: tuple  # (start_ns, end_ns) of the benchmark's slice
    spans: list  # (name, start_ns, end_ns, args): program spans in the slice
    scopes: dict  # decode-program instruction name -> scope, or empty


def scope_of(op_name: str) -> str:
    """The innermost named scope in an HLO ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def scope_map(hlo_text: str) -> dict:
    """Instruction name (without ``%``) -> scope, for every instruction of
    an optimized HLO module's text; those without metadata are unscoped."""
    out = {name: UNSCOPED for name in _NAMED.findall(hlo_text)}
    for name, op_name in _INSTR.findall(hlo_text):
        out[name] = scope_of(op_name)
    return out


def _stats(event) -> dict:
    return {k: v for k, v in event.stats if not k.startswith("_")}


def reduce_program(profile, decode_hlo: str | None = None) -> ProgramTrace:
    """The program spans of a ``jax.profiler.ProfileData`` that overlap the
    benchmark's slice, and the scope map of the decode program's optimized
    HLO text (``decode_hlo``; ``None`` leaves it empty)."""
    window, spans = None, []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s, end = float(e.start_ns), float(e.start_ns + e.duration_ns)
                if e.name == xplane.SLICE:
                    window = (s, end)
                elif e.name.startswith(PROGRAM_PREFIXES):
                    spans.append((e.name, s, end, _stats(e)))
    if window is None:
        raise ValueError(f"trace holds no {xplane.SLICE!r} span")
    lo, hi = window
    spans = sorted((sp for sp in spans if sp[2] >= lo and sp[1] <= hi),
                   key=lambda sp: (sp[1], -sp[2]))
    return ProgramTrace(window=window, spans=spans,
                        scopes=scope_map(decode_hlo) if decode_hlo else {})


def reduce_dir(log_dir, decode_hlo: str | None = None) -> ProgramTrace:
    """:func:`reduce_program` of the one ``.xplane.pb`` under a profiler
    log directory."""
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_program(ProfileData.from_file(str(files[-1])), decode_hlo)


# -- the host timeline ----------------------------------------------------

def merged(red: xplane.Reduction, prog: ProgramTrace) -> xplane.Reduction:
    """``red`` with the program spans among the spans that label its time:
    its ``label_at`` and ``idle_gaps`` then name the innermost span open,
    benchmark's or program's."""
    return dataclasses.replace(
        red, spans=red.spans + [(n, s, e) for n, s, e, _ in prog.spans])


def timeline(red: xplane.Reduction, prog: ProgramTrace) -> list:
    """The slice cut into segments ``(start_ns, end_ns, label)``, each
    labelled with the innermost span open over it (``"none"`` where none
    is)."""
    m = merged(red, prog)
    lo, hi = prog.window
    cuts = sorted({lo, hi} | {t for _, s, e in m.spans for t in (s, e)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts[:-1], cuts[1:]):
        label = m.label_at((a + b) / 2)
        if out and out[-1][2] == label:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def host_split(red: xplane.Reduction, prog: ProgramTrace) -> dict:
    """Seconds of the slice by innermost open span: ``{label: [wall_s,
    idle_s]}``, wall the time it was innermost, idle the part of it with
    no operation on the chip."""
    out: dict[str, list] = {}
    for a, b, label in timeline(red, prog):
        idle, _ = xplane._union(red.gaps, a, b)
        acc = out.setdefault(label, [0.0, 0.0])
        acc[0] += (b - a) / 1e9
        acc[1] += idle / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1][0]))


def decode_steps(prog: ProgramTrace) -> int:
    """Decode steps in the slice: the ``engine.dispatch`` spans."""
    return sum(name == "engine.dispatch" for name, *_ in prog.spans)


# -- the decode program by scope ------------------------------------------

def scope_time(red: xplane.Reduction, scopes: dict,
               module_pattern: str = DECODE_MODULE) -> tuple[dict, int]:
    """Device seconds of the operations inside the runs of the modules
    matching ``module_pattern``, by scope (``"unjoined"``: an operation
    whose name the scope map lacks), control flow that encloses other
    operations left out; and the number of those runs."""
    rm = re.compile(module_pattern)
    runs = sorted((s, e) for n, s, e in red.modules if rm.search(n))
    out: dict[str, float] = {}
    if not runs:
        return out, 0
    starts = np.asarray([s for s, _ in runs])
    ends = np.asarray([e for _, e in runs])
    for name, s, e in red.ops:
        if xplane.NESTING.match(name):
            continue
        i = np.searchsorted(starts, s, side="right") - 1
        if i < 0 or e > ends[i]:
            continue
        key = name.split(" = ")[0].lstrip("%")
        scope = scopes.get(key, UNJOINED)
        out[scope] = out.get(scope, 0.0) + (e - s) / 1e9
    return out, len(runs)


# -- readers --------------------------------------------------------------

def _inputs(ctx):
    red, prog = ctx.trace, getattr(ctx, "program", None)
    if red is None or prog is None:
        return None
    return red, prog


def control_ms_per_step(ctx):
    """Host wall milliseconds per decode step in the GEM control plane:
    the union of the ``CONTROL`` spans in the slice over its decode
    steps."""
    got = _inputs(ctx)
    if got is None:
        return None
    red, prog = got
    steps = decode_steps(prog)
    if steps == 0:
        return None
    busy, _ = xplane._union(
        [(s, e) for n, s, e, _ in prog.spans if n in CONTROL], *prog.window)
    return busy / 1e6 / steps


def control_idle_share(ctx):
    """Per cent of the slice in which no operation ran on the chip and the
    innermost open span was one of the ``CONTROL`` spans."""
    got = _inputs(ctx)
    if got is None or not got[1].spans or got[0].window_s <= 0:
        return None
    red, prog = got
    idle = host_split(red, prog)
    return 100.0 * sum(v[1] for k, v in idle.items() if k in CONTROL) \
        / red.window_s


def moe_dispatch_ms_per_step(ctx):
    """Device milliseconds per decode run of the operations under the
    ``route``, ``build_dispatch`` and ``combine`` scopes."""
    got = _inputs(ctx)
    if got is None or not got[1].scopes:
        return None
    by_scope, runs = scope_time(got[0], got[1].scopes)
    if runs == 0:
        return None
    return sum(by_scope.get(s, 0.0) for s in MOE_DISPATCH) * 1e3 / runs
