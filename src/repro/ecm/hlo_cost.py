"""Trip-count-aware cost model over optimized HLO text.

XLA's ``compiled.cost_analysis()`` visits each instruction once: a model
scanned over L layers under-reports FLOPs/bytes/collectives by ~L× (verified
in tests). This module re-derives the three roofline inputs from the
post-SPMD optimized module, multiplying every computation by its execution
count:

  * while bodies/conditions × known_trip_count (from backend_config; falls
    back to the max s32 constant in the condition, with a warning),
  * fusion/call/to_apply computations × their caller's multiplier,
  * conditional branch computations × their caller's multiplier (every
    branch — one runs per invocation, so this is an upper bound, but the
    skip branch of a ``pl.when``-predicated kernel block is an identity,
    so the bound equals the live-block cost),
  * dot FLOPs = 2 · |out| · K (contracting size from lhs),
  * elementwise FLOPs = |out| for arithmetic/transcendental opcodes,
  * bytes = Σ effective (operand + result) sizes per materialized
    instruction (fusion internals excluded — the fusion node is the buffer
    boundary). "Effective" matters: dynamic-slice reads a slice, not its
    full operand; in-place dynamic-update-slice writes the update, not the
    buffer; fusion operands that feed only slicing ops inside the fused
    computation count at slice granularity (otherwise a scan over L stacked
    layers would count the whole weight stack L times),
  * collective bytes weighted by a ring-schedule factor with the actual
    group size n: all-reduce 2(n-1)/n·b, all-gather/reduce-scatter/
    all-to-all (n-1)/n·b, collective-permute 1·b.

All quantities are per-chip (post-SPMD shapes are shard shapes).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import NamedTuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "token": 0,
}

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "rsqrt",
    "sqrt", "tanh", "logistic", "power", "negate", "abs", "sign", "floor",
    "ceil", "round-nearest-afz", "round-nearest-even", "select", "compare",
    "and", "or", "xor", "not", "clamp", "atan2", "cbrt", "cosine", "sine",
    "erf", "remainder", "shift-left", "shift-right-arithmetic",
    "shift-right-logical",
}

_BYTES_SKIP = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "while", "conditional", "call", "after-all", "add-dependency", "domain",
    "opt-barrier", "iota", "partition-id", "replica-id", "rng-bit-generator",
}

_COLLECTIVES = {
    "all-reduce": ("ar", 2.0), "all-gather": ("ag", 1.0),
    "reduce-scatter": ("rs", 1.0), "all-to-all": ("a2a", 1.0),
    "collective-permute": ("cp", 1.0), "ragged-all-to-all": ("a2a", 1.0),
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\]"
    r"(?:\{[^}]*\})?))\s+([a-z][a-z0-9\-]*)\(")
_TRIP_RE = re.compile(r'known_trip_count[":{ ]+n[": ]+"?(\d+)')
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_V1_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_CALLS_RE = re.compile(r"(?:calls|to_apply|condition|body|"
                       r"true_computation|false_computation)=%?([\w.\-]+)")
# conditional branches (pl.when lowers to these): every branch is priced
# at the caller's multiplier — an upper bound, since one branch runs per
# invocation, but the skip-branch of a predicated kernel block is an
# identity, so the bound IS the live-block cost the ECM model wants.
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERANDS_RE = re.compile(r"%([\w.\-]+)")


def _shape_elems_bytes(shape_str: str) -> tuple[int, int]:
    """(total elements, total bytes) across all array shapes in the string."""
    elems = 0
    nbytes = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        nbytes += n * _DTYPE_BYTES[dtype]
    return elems, nbytes


@dataclass
class Instr:
    name: str
    shape: str
    op: str
    line: str
    operands: list = field(default_factory=list)


@dataclass
class Computation:
    name: str
    instrs: list = field(default_factory=list)
    shapes: dict = field(default_factory=dict)   # symbol -> shape string


@dataclass
class HloCost:
    flops: float = 0.0
    dot_flops: float = 0.0
    elementwise_flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: dict = field(default_factory=dict)    # kind -> raw bytes
    collective_count: dict = field(default_factory=dict)
    weighted_collective_bytes: float = 0.0                  # ring-schedule
    warnings: list = field(default_factory=list)


def _parse_computations(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    current: Computation | None = None
    for line in text.splitlines():
        m = _COMP_HEADER_RE.match(line)
        if m:
            current = Computation(name=m.group(2))
            comps[current.name] = current
            # register parameters declared in the header
            for pm in re.finditer(r"([\w.\-]+):\s*((?:\([^)]*\)|[a-z0-9]+"
                                  r"\[[0-9,]*\]))", line):
                current.shapes[pm.group(1)] = pm.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        if current is None:
            continue
        im = _INSTR_RE.match(line)
        if im:
            name, shape, op = im.group(1), im.group(2), im.group(3)
            rest = line[im.end():]
            # operands: %refs inside the first paren group (cheap cut: up to
            # the first "), " attribute boundary)
            args = rest.split("), ")[0]
            operands = _OPERANDS_RE.findall(args)
            instr = Instr(name=name, shape=shape, op=op, line=line,
                          operands=operands)
            current.instrs.append(instr)
            current.shapes[name] = shape
    return comps


def _trip_count(instr: Instr, comps: dict, warnings: list) -> int:
    m = _TRIP_RE.search(instr.line)
    if m:
        return int(m.group(1))
    cm = _CALLS_RE.findall(instr.line)
    cond_name = None
    m2 = re.search(r"condition=%?([\w.\-]+)", instr.line)
    if m2:
        cond_name = m2.group(1)
    if cond_name and cond_name in comps:
        consts = []
        for i in comps[cond_name].instrs:
            c = re.search(r"s32\[\]\s+constant\((\d+)\)", i.line)
            if c:
                consts.append(int(c.group(1)))
        if consts:
            warnings.append(f"while {instr.name}: trip from cond constant "
                            f"{max(consts)}")
            return max(consts)
    warnings.append(f"while {instr.name}: unknown trip count, assuming 1")
    return 1


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_V1_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


_SLICING_OPS = {"dynamic-slice", "gather", "slice"}
# dtype/layout pass-throughs followed transparently inside fusion analysis:
# XLA CPU legalizes bf16 via f32 convert round-trips that a TPU build never
# materializes, so converts must not turn a sliced access into a full read.
_TRANSPARENT = {"convert", "bitcast", "copy", "reshape", "transpose"}


def _fusion_operand_bytes(called: Computation, k: int) -> float | None:
    """Effective bytes read for parameter k of a fused computation, or None
    for 'count the full operand'. Follows convert/bitcast chains."""
    pname = None
    for i in called.instrs:
        if i.op == "parameter" and f"parameter({k})" in i.line:
            pname = i.name
            break
    if pname is None:
        return None
    aliases = {pname}
    # resolve transparent single-input chains rooted at the parameter
    changed = True
    while changed:
        changed = False
        for i in called.instrs:
            if (i.op in _TRANSPARENT and i.operands
                    and i.operands[0] in aliases and i.name not in aliases):
                aliases.add(i.name)
                changed = True
    total = 0.0
    for i in called.instrs:
        hit = [o for o in i.operands if o in aliases]
        if not hit or i.name in aliases:
            continue
        if i.op in _SLICING_OPS and i.operands[0] in aliases:
            _, b = _shape_elems_bytes(i.shape)
            total += b
        elif i.op == "dynamic-update-slice" and i.operands[0] in aliases:
            continue   # in-place buffer pass-through: aliased, no read
        else:
            return None  # consumed wholesale somewhere
    return total


def _fusion_out_bytes(called: Computation, default: float) -> float:
    """Effective bytes written by a fusion: in-place DUS roots (possibly
    wrapped in convert/bitcast) write only the update window."""
    root = None
    for i in called.instrs:
        if "ROOT" in i.line:
            root = i
    if root is None and called.instrs:
        root = called.instrs[-1]
    by_name = {i.name: i for i in called.instrs}
    seen = 0
    while (root is not None and root.op in _TRANSPARENT and root.operands
           and root.operands[0] in by_name and seen < 8):
        root = by_name[root.operands[0]]
        seen += 1
    if root is not None and root.op == "dynamic-update-slice" \
            and len(root.operands) >= 2:
        upd = root.operands[1]
        if upd in called.shapes:
            _, b = _shape_elems_bytes(called.shapes[upd])
            return b
    return default


def _effective_bytes(instr: Instr, comp: Computation,
                     comps: dict[str, Computation]) -> float:
    """Effective (read + write) bytes of one materialized instruction."""
    _, out_bytes = _shape_elems_bytes(instr.shape)
    op = instr.op

    def opsize(name):
        if name in comp.shapes:
            _, b = _shape_elems_bytes(comp.shapes[name])
            return b
        return 0.0

    if op == "copy" and instr.operands:
        # same-shape copies are loop-carry aliasing artifacts of the CPU
        # pipeline; TPU buffer assignment elides them
        src = instr.operands[0]
        if src in comp.shapes:
            se, _ = _shape_elems_bytes(comp.shapes[src])
            oe, _ = _shape_elems_bytes(instr.shape)
            if se == oe:
                return 0.0
    if op in _SLICING_OPS:
        return 2.0 * out_bytes + sum(opsize(o) for o in instr.operands[1:])
    if op == "dynamic-update-slice":
        upd = opsize(instr.operands[1]) if len(instr.operands) > 1 else 0.0
        return 2.0 * upd
    if op == "scatter":
        upd = opsize(instr.operands[-1]) if instr.operands else 0.0
        return 2.0 * upd + sum(opsize(o) for o in instr.operands[1:-1])
    if op == "fusion":
        cm = re.search(r"calls=%?([\w.\-]+)", instr.line)
        called = comps.get(cm.group(1)) if cm else None
        if called is None:
            return out_bytes + sum(opsize(o) for o in instr.operands)
        total = _fusion_out_bytes(called, out_bytes)
        for k, o in enumerate(instr.operands):
            eff = _fusion_operand_bytes(called, k)
            total += opsize(o) if eff is None else min(eff, opsize(o) * 4)
        return total
    return out_bytes + sum(opsize(o) for o in instr.operands)


def analyze(text: str, *, default_group: int = 1) -> HloCost:
    comps = _parse_computations(text)
    cost = HloCost()

    # entry computation: the one marked ENTRY (re-scan raw text)
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HEADER_RE.match(line)
            if m:
                entry = m.group(2)
            break
    if entry is None or entry not in comps:
        cost.warnings.append("no ENTRY computation found")
        return cost

    # ---- multipliers + fusion-internal marking --------------------------
    mult: dict[str, float] = {entry: 1.0}
    internal: set[str] = set()
    order = [entry]
    seen = {entry}
    i = 0
    while i < len(order):
        cname = order[i]
        i += 1
        comp = comps[cname]
        for instr in comp.instrs:
            refs = _CALLS_RE.findall(instr.line)
            bm = _BRANCHES_RE.search(instr.line)
            if bm:
                refs += _OPERANDS_RE.findall(bm.group(1))
            if not refs:
                continue
            if instr.op == "while":
                trip = _trip_count(instr, comps, cost.warnings)
                for r in refs:
                    if r in comps:
                        mult[r] = mult.get(r, 0.0) + mult[cname] * trip
                        if r not in seen:
                            seen.add(r)
                            order.append(r)
            else:
                is_internal = ("calls=" in instr.line
                               or "to_apply=" in instr.line)
                for r in refs:
                    if r in comps:
                        mult[r] = mult.get(r, 0.0) + mult[cname]
                        if is_internal:
                            internal.add(r)
                        if r not in seen:
                            seen.add(r)
                            order.append(r)

    # ---- per-instruction accounting --------------------------------------
    for cname in order:
        comp = comps[cname]
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        materialized = cname not in internal
        for instr in comp.instrs:
            out_elems, out_bytes = _shape_elems_bytes(instr.shape)
            op = instr.op

            # flops
            if op == "dot":
                k = 1
                lhs = instr.operands[0] if instr.operands else None
                cdims = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}",
                                  instr.line)
                if lhs and lhs in comp.shapes and cdims:
                    dims_m = _SHAPE_RE.search(comp.shapes[lhs])
                    if dims_m:
                        lhs_dims = [int(d) for d in
                                    dims_m.group(2).split(",") if d]
                        for ci in cdims.group(1).split(","):
                            if ci:
                                k *= lhs_dims[int(ci)]
                f = 2.0 * out_elems * k
                cost.dot_flops += f * m
                cost.flops += f * m
            elif op == "convolution":
                rhs = instr.operands[1] if len(instr.operands) > 1 else None
                k = 1
                if rhs and rhs in comp.shapes:
                    k_elems, _ = _shape_elems_bytes(comp.shapes[rhs])
                    k = max(k_elems, 1)
                f = 2.0 * out_elems * k
                cost.dot_flops += f * m
                cost.flops += f * m
            elif op in _ELEMENTWISE:
                cost.elementwise_flops += out_elems * m
                cost.flops += out_elems * m
            elif op in ("reduce", "reduce-window"):
                in_elems = 0
                for o in instr.operands[:1]:
                    if o in comp.shapes:
                        in_elems, _ = _shape_elems_bytes(comp.shapes[o])
                cost.elementwise_flops += in_elems * m
                cost.flops += in_elems * m

            # bytes (materialized instructions only, effective sizes)
            if materialized and op not in _BYTES_SKIP:
                cost.bytes_accessed += _effective_bytes(instr, comp, comps) * m

            # collectives
            base = op[:-6] if op.endswith("-start") else op
            if base in _COLLECTIVES and not op.endswith("-done"):
                kind, factor = _COLLECTIVES[base]
                n = _group_size(instr.line, default_group)
                frac = (n - 1) / n if n > 1 else 0.0
                opb = 0
                for o in instr.operands:
                    if o in comp.shapes:
                        _, b = _shape_elems_bytes(comp.shapes[o])
                        opb += b
                vol = opb if base != "all-gather" else out_bytes
                cost.collective_bytes[base] = (
                    cost.collective_bytes.get(base, 0.0) + vol * m)
                cost.collective_count[base] = (
                    cost.collective_count.get(base, 0) + m)
                cost.weighted_collective_bytes += vol * factor * frac * m

    return cost


_LAYOUT_RE = re.compile(r"\[([0-9,]*)\]\{([0-9,]*)")


class PoolCopy(NamedTuple):
    name: str             # the copy instruction
    dims: tuple           # its result's shape
    layout: str           # its result's minor-to-major order
    default: bool         # whether that is the default row-major order


def pool_copies(text: str, num_blocks: int) -> list[PoolCopy]:
    """Every ``copy`` in optimized HLO ``text`` whose result is shaped like
    a KV pool with ``num_blocks`` blocks: one layer's [nb, ...] or
    [1, nb, ...], or a stacked pool [L, nb, ...]. A serving launch that
    re-lays a pool out shows a copy into a non-default layout here."""
    out = []
    for comp in _parse_computations(text).values():
        for ins in comp.instrs:
            m = _LAYOUT_RE.search(ins.shape)
            if ins.op != "copy" or m is None:
                continue
            dims = tuple(int(d) for d in m.group(1).split(",") if d)
            if num_blocks not in dims[:2]:
                continue
            order = [int(d) for d in m.group(2).split(",") if d]
            out.append(PoolCopy(ins.name, dims, m.group(2),
                                order == sorted(order, reverse=True)))
    return out
