"""Post-SPMD HLO text analysis: collective bytes with loop trip attribution.

``lax.scan`` lowers to an HLO while loop whose body is printed once, so a
naive text scan undercounts every collective inside the layer stack by a
factor of L. This module parses the computation graph structure:

  1. split the module into computation blocks,
  2. find every ``while`` instruction, its condition/body computations, and
     its trip count (the integer constant feeding the loop-bound slot of the
     init tuple, located through the condition's ROOT compare),
  3. propagate multiplicative trip factors down the computation tree,
  4. sum per-collective operand bytes × enclosing trip product.

Operand refs in optimized HLO don't carry inline types, so operand bytes are
derived from the result shape: all-gather operand = result / group_size,
reduce-scatter operand = result × group_size, others 1:1. ``wire_bytes``
applies the ring-transfer factor (AR: 2(g−1)/g, AG/RS: (g−1)/g) — the
quantity an ICI link actually carries.
"""
from __future__ import annotations

import re

__all__ = [
    "collective_stats", "arrays_shaped", "aliased_parameters",
    "expert_weight_shapes", "COLLECTIVES",
]

COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_WHILE_RE = re.compile(
    r"while\((%[\w.\-]+)\), condition=(%[\w.\-]+), body=(%[\w.\-]+)"
)
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.*)$")
_PARAM_RE = re.compile(r"parameter\((\d+)\)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
# the opcode follows the result type: the first word that opens a paren
# after whitespace (layouts such as ``{1,0:T(8,128)}`` open theirs after
# a colon or a paren)
_OPCODE_RE = re.compile(r"\s([a-z][\w-]*)\(")
# ``input_output_alias={ {1}: (12, {}, may-alias), ... }``: parameter numbers
_ALIAS_RE = re.compile(r"\{[\d,\s]*\}:\s*\((\d+),")
_RESULT_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+)\[([\d,]*)\])\S*\s+([\w-]+?)(?:-start)?\("
)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUP_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUP_LIST_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 1


def _split_computations(text: str) -> tuple[dict[str, list[str]], str | None]:
    comps: dict[str, list[str]] = {}
    entry = None
    cur: str | None = None
    for line in text.splitlines():
        m = _COMP_HDR_RE.match(line.strip())
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            comps[cur] = []
            if line.strip().startswith("ENTRY"):
                entry = cur
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
                continue
            comps[cur].append(line)
    return comps, entry


def _defs(comp_lines: list[str]) -> dict[str, str]:
    out = {}
    for line in comp_lines:
        m = _DEF_RE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _trip_count(
    while_line: str, comp_lines: list[str], comps: dict[str, list[str]]
) -> int | None:
    """Trip count of one while loop.

    XLA annotates analyzable loops with backend_config known_trip_count;
    fall back to chasing the constant feeding the condition's compare bound.
    """
    tm = _TRIP_RE.search(while_line)
    if tm:
        return int(tm.group(1))
    m = _WHILE_RE.search(while_line)
    if not m:
        return None
    init_name, cond_name, _ = m.groups()
    cond_lines = comps.get(cond_name, [])
    cond_defs = _defs(cond_lines)
    # ROOT compare(%a, %b): find which operand is a parameter, get its index
    root = next((r for n, r in cond_defs.items() if "compare(" in r), None)
    if root is None:
        return None
    ops = re.findall(r"compare\((%[\w.\-]+),\s*(%[\w.\-]+)\)", root)
    if not ops:
        return None
    bound_idx = None
    for name in ops[0]:
        d = cond_defs.get(name, "")
        pm = _PARAM_RE.search(d)
        cm = _CONST_RE.search(d)
        if cm:  # bound directly as constant in cond
            return int(cm.group(1))
        if pm:
            bound_idx = int(pm.group(1))  # last param wins (bound usually 2nd)
    if bound_idx is None:
        return None
    # resolve the init tuple element at bound_idx
    local_defs = _defs(comp_lines)
    init_def = local_defs.get(init_name, "")
    tup = re.search(r"tuple\(([^)]*)\)", init_def)
    if tup:
        elems = [e.strip() for e in tup.group(1).split(",")]
        if bound_idx < len(elems):
            elem = elems[bound_idx]
            for _ in range(3):  # follow copy/convert chains
                d = local_defs.get(elem, "")
                cm = _CONST_RE.search(d)
                if cm:
                    return int(cm.group(1))
                nxt = re.search(r"(?:copy|convert|bitcast)\((%[\w.\-]+)\)", d)
                if not nxt:
                    break
                elem = nxt.group(1)
    return None


_CALL_RE = re.compile(r"(?:calls|to_apply)=(%[\w.\-]+)")
_DOT_RE = re.compile(
    r"dot\((%[\w.\-]+),\s*(%[\w.\-]+)\)"
)
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OPERANDS_RE = re.compile(r"\((%[\w.\-]+(?:,\s*%[\w.\-]+)*)\)")


def _build_factors(text: str, default_trip: int = 1):
    """(computations, entry, comp→execution-count factor, unresolved count).

    Walks entry → while bodies (× trip count) → fusion/call targets, so every
    executed computation carries how many times it runs per step.
    """
    comps, entry = _split_computations(text)
    if entry is None:
        entry = next(iter(comps), None)
    factors: dict[str, float] = {}
    unresolved: list[str] = []

    def visit(comp: str, factor: float):
        if comp not in comps:
            return
        factors[comp] = factors.get(comp, 0.0) + factor
        for line in comps[comp]:
            m = _WHILE_RE.search(line)
            if m:
                _, cond, body = m.groups()
                trips = _trip_count(line, comps[comp], comps)
                if trips is None:
                    trips = default_trip
                    unresolved.append(body)
                visit(body, factor * trips)
                visit(cond, factor)
                continue
            cm = _CALL_RE.search(line)
            if cm and ("fusion(" in line or " call(" in line
                       or "conditional(" in line):
                visit(cm.group(1), factor)

    if entry:
        visit(entry, 1.0)
    return comps, entry, factors, unresolved


def _line_shape_bytes(defline: str) -> int | None:
    """Total byte size of an instruction's result (tuple-aware)."""
    m = re.match(r"(?:\(([^)]*)\)|(\w+)\[([\d,]*)\])", defline)
    if not m:
        return None
    tup, dt, dims = m.groups()
    if tup is not None:
        return sum(
            _shape_bytes(d, s) for d, s in _SHAPE_RE.findall(tup)
            if d in _DTYPE_BYTES
        )
    if dt in _DTYPE_BYTES:
        return _shape_bytes(dt, dims)
    return None


def _shape_dims(defline: str) -> list[int] | None:
    m = re.match(r"(\w+)\[([\d,]*)\]", defline)
    if not m or m.group(1) not in _DTYPE_BYTES:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


def compute_stats(text: str, *, default_trip: int = 1) -> dict:
    """Trip-aware HLO FLOPs and HBM bytes from the optimized module text.

    XLA's ``cost_analysis()`` does not always multiply nested/transformed
    while bodies by their trip counts (training loops undercount ~L×), so we
    re-derive both quantities structurally:

    * **flops**: 2·(result elements)·(contraction size) per ``dot``, walked
      with execution factors. Contraction size comes from the lhs operand's
      resolved shape and ``lhs_contracting_dims``.
    * **bytes**: per *executed, top-level* instruction, result + operand
      bytes (fusion internals excluded — a fusion's traffic is its operands
      and result, which is exactly how the CPU/TPU fusion model works).
    """
    comps, entry, factors, unresolved = _build_factors(text, default_trip)
    fused: set[str] = set()
    for lines in comps.values():
        for line in lines:
            if "fusion(" in line:
                m = _CALL_RE.search(line)
                if m:
                    fused.add(m.group(1))

    def _dus_update_bytes(comp_name: str) -> int | None:
        """If a fused computation's root is a dynamic-update-slice (an
        in-place buffer write, e.g. scan's ys accumulation), the fusion's
        real traffic is the update window, not the full result buffer."""
        lines = comps.get(comp_name, [])
        defs = _defs(lines)
        for line in lines:
            ls = line.strip()
            if ls.startswith("ROOT ") and " dynamic-update-slice(" in ls:
                ops = re.findall(r"%[\w.\-]+", ls.split("dynamic-update-slice(", 1)[1])
                if len(ops) >= 2:
                    ud = defs.get(ops[1])
                    if ud:
                        return _line_shape_bytes(ud)
        return None

    # structural ops that move no HBM data (views / tuple plumbing; loop-
    # carry copies alias in place on TPU for donated buffers). Control-flow
    # headers (while/conditional/call/fusion) are skipped too — their bodies'
    # instructions carry the traffic.
    free_ops = (
        "tuple(", "get-tuple-element(", "parameter(", "constant(",
        "bitcast(", "reshape(", "after-all(", "iota(",
        "copy(", "copy-start(", "copy-done(",
        "while(", "conditional(", "call(",
    )
    total_flops = 0.0
    total_bytes = 0.0
    for comp, lines in comps.items():
        f = factors.get(comp)
        if f is None or f == 0.0:
            continue
        defs = _defs(lines)
        mem_side = comp not in fused  # fusion internals: no HBM traffic
        for line in lines:
            dm = _DEF_RE.match(line)
            if not dm:
                continue
            _, rest = dm.groups()
            # ---- flops: dot ops (counted wherever they live) ----
            dd = _DOT_RE.search(rest)
            if dd:
                out_dims = _shape_dims(rest)
                lhs = defs.get(dd.group(1), "")
                lhs_dims = _shape_dims(lhs)
                cm = _CONTRACT_RE.search(rest)
                if out_dims is not None and lhs_dims is not None and cm:
                    contract = 1
                    for idx in cm.group(1).split(","):
                        if idx:
                            contract *= lhs_dims[int(idx)]
                    n_out = 1
                    for d in out_dims:
                        n_out *= d
                    total_flops += 2.0 * n_out * contract * f
            # ---- bytes: result-centric model over executed instructions:
            # each materialized buffer is written once and read ~once
            # downstream (2× result bytes); views/tuples are free; a
            # dynamic-update-slice touches only its update window.
            if not mem_side:
                continue
            if rest.startswith("("):
                # tuple-valued results are structural (while carries,
                # optimization barriers, sort wrappers): their traffic is
                # carried by the element-producing instructions
                continue
            om = re.match(r"\S+\s+([\w\-]+)\(", rest)
            opcode = om.group(1) if om else ""
            body = opcode + "("
            if any(body == op for op in free_ops):
                continue
            if opcode == "fusion":
                cm2 = _CALL_RE.search(rest)
                if cm2:
                    ub = _dus_update_bytes(cm2.group(1))
                    if ub is not None:
                        total_bytes += 2.0 * ub * f
                        continue
            if body == "dynamic-update-slice(":
                ops = re.findall(r"%[\w.\-]+", body)
                if len(ops) >= 2:
                    ud = defs.get(ops[1])
                    if ud:
                        ub = _line_shape_bytes(ud)
                        if ub is not None:
                            total_bytes += 2.0 * ub * f
                continue
            rb = _line_shape_bytes(rest)
            if rb is not None:
                total_bytes += 2.0 * rb * f
    return {
        "flops": total_flops,
        "bytes": total_bytes,
        "unresolved_loops": len(unresolved),
    }


def collective_stats(text: str, *, default_trip: int = 1) -> dict:
    """Collective operand/wire bytes with while-loop trip multiplication."""
    comps, entry, factors, unresolved = _build_factors(text, default_trip)

    out = {k: 0.0 for k in COLLECTIVES}
    wire = {k: 0.0 for k in COLLECTIVES}
    counts = {k: 0.0 for k in COLLECTIVES}
    for comp, lines in comps.items():
        f = factors.get(comp)
        if f is None:
            # computation not reached through entry/while tree: fusions and
            # reducers — collectives never live there, but double-check
            f = 1.0
            if not any(k + "(" in ln or k + "-start(" in ln
                       for ln in lines for k in COLLECTIVES):
                continue
        for line in lines:
            ls = line.strip()
            m = _RESULT_RE.search(ls)
            if not m:
                continue
            tuple_part, dt, dims, op = m.groups()
            if op not in COLLECTIVES:
                continue
            if tuple_part is not None:
                result = sum(
                    _shape_bytes(d, s)
                    for d, s in _SHAPE_RE.findall(tuple_part)
                    if d in _DTYPE_BYTES
                )
            elif dt in _DTYPE_BYTES:
                result = _shape_bytes(dt, dims)
            else:
                continue
            g = _group_size(ls)
            if op == "all-gather":
                operand = result / g
                w = result * (g - 1) / g
            elif op == "reduce-scatter":
                operand = result * g
                w = operand * (g - 1) / g
            elif op == "all-reduce":
                operand = result
                w = 2.0 * result * (g - 1) / g
            else:
                operand = result
                w = result
            out[op] += operand * f
            wire[op] += w * f
            counts[op] += f
    return {
        "bytes": out,
        "wire_bytes": wire,
        "counts": counts,
        "total_bytes": float(sum(out.values())),
        "total_wire_bytes": float(sum(wire.values())),
        "unresolved_loops": len(unresolved),
    }


def arrays_shaped(text: str, dtype: str, shapes, opcodes=None) -> list[str]:
    """Instructions of ``text``, fusion bodies included, whose result is a
    ``dtype`` array ending in one of ``shapes`` (leading dims, such as a
    stacked layer axis, are ignored): e.g. an f32 copy of a bf16 weight.
    A tuple result (``copy-start``'s) counts when one of its elements
    does; ``opcodes``, if given, keeps only instructions of those opcodes.
    """
    want = {tuple(s) for s in shapes}
    hits = []
    for line in text.splitlines():
        m = _DEF_RE.match(line)
        if m is None:
            continue
        op = _OPCODE_RE.search(m.group(2))
        if op is None or (opcodes is not None and op.group(1) not in opcodes):
            continue
        for dt, dims in _SHAPE_RE.findall(m.group(2)[:op.start()]):
            dims = tuple(int(d) for d in dims.split(",") if d)
            if dt == dtype and any(dims[len(dims) - len(w):] == w
                                   for w in want if len(dims) >= len(w)):
                hits.append(line.strip())
                break
    return hits


def aliased_parameters(text: str) -> list[str]:
    """The entry computation's parameter instructions that the module's
    ``input_output_alias`` gives an output buffer: the arguments a
    donating jit writes in place."""
    header = next((ln for ln in text.splitlines()
                   if ln.startswith("HloModule")), "")
    alias = header.partition("input_output_alias=")[2]
    alias = alias.partition("entry_computation_layout=")[0]
    aliased = {int(n) for n in _ALIAS_RE.findall(alias)}
    comps, entry = _split_computations(text)
    return [
        line.strip() for line in comps.get(entry, [])
        if (p := _PARAM_RE.search(line)) and int(p.group(1)) in aliased
    ]


def expert_weight_shapes(config, model_shards: int = 1) -> list[tuple]:
    """Per-chip (E_v, D, F) and (E_v, F, D) shapes of one layer's expert
    weights with the experts split over ``model_shards`` chips."""
    Ev = config.num_experts * config.expert_tp // model_shards
    D, F = config.d_model, config.expert_d_ff // config.expert_tp
    return [(Ev, D, F), (Ev, F, D)]
