"""Collective-traffic extraction from lowered/compiled HLO text.

``compiled.cost_analysis()`` exposes FLOPs and bytes-accessed but not
collective traffic; per the assignment we parse the (optimized) HLO and
sum the *result* shapes of every collective op as the bytes-moved proxy
(for all-reduce the result equals the operand; for all-gather it is the
gathered size, i.e. the received volume — a per-device upper bound that
is the quantity the ICI roofline term wants).  ``loop_body_ops`` lists
what runs inside a compiled program's loop bodies.
"""

from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c128": 16,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

# e.g.:  %ag = bf16[16,1024]{1,0} all-gather(%x), ...
#        %t = (f32[8,2]{...}, f32[8,2]{...}) all-to-all(...)
_LINE_RE = re.compile(
    r"=\s*(?P<shapes>\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<op>" + "|".join(COLLECTIVE_OPS) + r")\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> "dict[str, int]":
    """Per-collective-op-type byte totals (plus 'total')."""
    out: dict[str, int] = defaultdict(int)
    for m in _LINE_RE.finditer(hlo_text):
        op = m.group("op")
        nbytes = sum(_shape_bytes(dt, dims)
                     for dt, dims in _SHAPE_RE.findall(m.group("shapes")))
        out[op] += nbytes
        out["total"] += nbytes
    return dict(out)


def collective_counts(hlo_text: str) -> "dict[str, int]":
    out: dict[str, int] = defaultdict(int)
    for m in _LINE_RE.finditer(hlo_text):
        out[m.group("op")] += 1
    return dict(out)


# XLA:CPU hoisted kLoop convert fusions (`%wrapped_convert.N = f32[...]
# fusion(%param.M)`) and plain converts.  The fusion def and the convert
# inside its called computation describe the same buffer, so when wrapped
# fusions exist only those are summed.
_WRAPPED_CONVERT_RE = re.compile(
    r"%wrapped_convert[\w.]*\s*=\s*f32\[([0-9,]+)\][^=]*fusion\(")
_PLAIN_CONVERT_RE = re.compile(
    r"%convert[\w.]*\s*=\s*f32\[([0-9,]+)\][^=]*convert\(")


def _sum_shapes(matches, min_bytes):
    total = 0
    for dims in matches:
        n = 1
        for d in dims.split(","):
            n *= int(d)
        if n * 4 >= min_bytes:
            total += n * 4
    return total


def bf16_convert_artifact_bytes(hlo_text: str,
                                min_bytes: int = 64 << 20) -> int:
    """CPU-backend artifact detector: XLA CPU has no native bf16 dot, so
    it converts bf16 operands to f32 — and hoists loop-invariant weight /
    cache conversions OUT of layer scans, materializing the full stack at
    4 bytes/elem.  A TPU backend consumes bf16 in the MXU directly, so
    these buffers do not exist on the target.  Returns the total bytes of
    large (>= min_bytes) f32 convert results, which we subtract to report
    target-corrected per-device memory."""
    wrapped = _sum_shapes(_WRAPPED_CONVERT_RE.findall(hlo_text), min_bytes)
    if wrapped:
        return wrapped
    return _sum_shapes(_PLAIN_CONVERT_RE.findall(hlo_text), min_bytes)


def op_histogram(hlo_text: str, top: int = 20):
    """Most frequent HLO op names — remat/redundancy smell test (§Perf)."""
    ops = re.findall(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z][a-z0-9-]*)\(",
                     hlo_text)
    hist: dict[str, int] = defaultdict(int)
    for o in ops:
        hist[o] += 1
    return sorted(hist.items(), key=lambda kv: -kv[1])[:top]


# a computation's header starts in column 0 and opens its body:
#   %fused_computation.3 (param_0: bf16[2,8]) -> bf16[2,8] {
_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
# an array-valued instruction (a tuple's shape holds spaces and is
# skipped):   %copy.5 = bf16[2,65,16]{2,1,0:T(8,128)} copy(%p), ...
_ARRAY_INSTR_RE = re.compile(
    r"^\s+(?:ROOT )?%?[\w.\-]+ = [a-z0-9]+\[([0-9,]*)\]\S* "
    r"([a-z][a-z0-9-]*)\(")
_CALLED_RE = re.compile(
    r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")


def loop_body_ops(hlo_text: str) -> "list[tuple[str, tuple[int, ...]]]":
    """``(opcode, result dims)`` of every array-valued instruction that
    runs inside a ``while`` loop's body — the fusions, reductions and
    nested loops the body calls included — so a test can ask what a
    scanned step moves per iteration, whatever the backend fused it
    into."""
    comps: dict[str, list[str]] = {}
    lines: "list[str] | None" = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m:
            lines = comps.setdefault(m.group(1), [])
        elif lines is not None and line.startswith(" "):
            lines.append(line)

    def called(line):
        for one, many in _CALLED_RE.findall(line):
            yield from ([one] if one else
                        [c.strip().lstrip("%") for c in many.split(",")])

    stack = [m.group(1) for body in comps.values() for line in body
             if " while(" in line
             for m in re.finditer(r"body=%?([\w.\-]+)", line)]
    seen: set[str] = set()
    ops = []
    while stack:
        name = stack.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            stack.extend(called(line))
            m = _ARRAY_INSTR_RE.match(line)
            if m:
                dims = tuple(int(d) for d in m.group(1).split(",") if d)
                ops.append((m.group(2), dims))
    return ops
