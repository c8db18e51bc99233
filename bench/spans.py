"""Reduce a profiler trace (``.xplane.pb``) to the program's own spans:
the engine's phases and the step programs' model scopes, in seconds on
the trace's clock.

The engine records each phase of an iteration as a host annotation named
``engine.<kind>`` (``runtime/telemetry.py``: ``iteration``,
``schedule``, ``plan``, ``prefill_chunk``, ``decode``, ``megastep``,
``dispatch``, ``sync``, ``reconcile``, ...).  The served step programs
name their parts with ``jax.named_scope`` (``embed``, ``norm``,
``attention``, ``mlp``, ``lm_head``, ``sample``).  On a TPU the device
operations' events carry no name stack: the profiler keeps each traced
program's HLO on its ``/host:metadata`` plane (the stat "Hlo Proto" of
an event named as the program's execution, ``jit_run(<id>)``), and an
instruction's ``op_name`` metadata there is its name stack.  The layer
scan's own slices, write-backs and the compiler's copies of the carried
KV pool sit under no model scope (``unscoped``).

``bench/trace.py`` reads the same file for the benchmark's existing
metrics and is left as it is; this module adds to it.
"""

from __future__ import annotations

import bisect
import pathlib
from dataclasses import dataclass, field

import numpy as np

from bench import trace

PREFIX = "engine."
#: the model's top-level scopes in the served step programs
SCOPES = ("embed", "norm", "attention", "ssm", "mlp", "lm_head", "sample")
UNSCOPED = "unscoped"
#: the decode programs: the megastep's scan and the single step
DECODE = ("jit_run", "jit_step")
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


@dataclass
class Spans:
    #: [(kind, start_s, end_s)] of the engine's host annotations (not
    #: the harness's ``engine.step``), by start
    host: "list[tuple[str, float, float]]" = field(default_factory=list)
    #: per device: [(program, scope, op, start_s, end_s)]
    ops: "list[list[tuple[str, str, str, float, float]]]" = field(
        default_factory=list)


def scope_of(stack: str) -> str:
    """The first model scope in a name stack such as
    ``jit(run)/while/body/closed_call/attention/kv_gather/gather``, or
    ``unscoped``."""
    for part in stack.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


# -- protobuf wire format: just what the metadata plane needs ---------------

def _varint(buf, i: int) -> "tuple[int, int]":
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a serialized message:
    varints as ints, everything else as a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {wire}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _first(buf, number: int):
    return next((v for f, v in _fields(buf) if f == number), None)


def _op_names(hlo_proto) -> "dict[str, str]":
    """Instruction name -> ``op_name`` metadata of a serialized
    ``xla.HloProto`` (hlo_module 1 > computations 3 > instructions 2 >
    name 1, metadata 7 > op_name 2)."""
    out = {}
    module = _first(hlo_proto, 1)
    for f, comp in _fields(module if module is not None else b""):
        if f != 3:
            continue
        for g, ins in _fields(comp):
            if g != 2:
                continue
            name = stack = ""
            for h, v in _fields(ins):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    op = _first(v, 2)
                    stack = bytes(op).decode() if op is not None else ""
            out[name] = stack
    return out


def name_stacks(path) -> "dict[str, dict[str, str]]":
    """Per program execution name (``jit_run(<id>)``), each instruction's
    name stack, from the HLO the profiler keeps on its metadata plane
    (XSpace planes 1 > XPlane name 2, event_metadata 4, stat_metadata 5;
    XEventMetadata name 2, stats 5; XStat metadata_id 1, bytes 6)."""
    buf = memoryview(pathlib.Path(path).read_bytes())
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name = _first(plane, 2)
        if name is None or bytes(name).decode() != METADATA_PLANE:
            continue
        events, stat_names = [], {}
        for g, v in _fields(plane):
            if g == 4:
                events.append(_first(v, 2))
            elif g == 5:
                meta = _first(v, 2)
                if meta is not None:
                    stat_names[_first(meta, 1) or 0] = \
                        bytes(_first(meta, 2) or b"").decode()
        out = {}
        for meta in events:
            if meta is None:
                continue
            prog = bytes(_first(meta, 2) or b"").decode()
            for h, stat in _fields(meta):
                if h == 5 and stat_names.get(_first(stat, 1) or 0) == HLO_STAT:
                    out[prog] = _op_names(_first(stat, 6) or b"")
        return out
    return {}


# -- the reduction -------------------------------------------------------------

def _in_program(progs, starts, t: float) -> str:
    """The execution name of the program that holds time ``t``, or ""."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and progs[i][1] <= t <= progs[i][2]:
        return progs[i][0]
    return ""


def load(path) -> Spans:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    stacks = name_stacks(path)
    sp = Spans()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and ":CPU" not in plane.name:
            progs, raw = [], []
            for line in plane.lines:
                if "Modules" in line.name:
                    progs += [(e.name.strip(), e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9)
                              for e in line.events]
                elif line.name == "XLA Ops":
                    raw += [(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
            progs.sort(key=lambda p: p[1])
            starts = [p[1] for p in progs]
            ops = []
            for name, s, e in raw:
                run = _in_program(progs, starts, s)
                ins = name.partition(" = ")[0].strip().lstrip("%")
                stack = stacks.get(run, {}).get(ins, "")
                ops.append((trace.program_name(run), scope_of(stack), name,
                            s, e))
            if ops:
                sp.ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                sp.host += [(e.name[len(PREFIX):], e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events
                            if e.name.startswith(PREFIX)
                            and e.name not in trace.ANNOTATIONS]
    sp.host.sort(key=lambda h: h[1])
    return sp


def _covering(sp: Spans, s: float, e: float):
    """The engine spans that overlap ``[s, e]``."""
    for kind, hs, he in sp.host:
        if hs > e:
            break
        if he > s:
            yield kind, hs, he


def phase(sp: Spans, s: float, e: float) -> "str | None":
    """The innermost engine span covering more than half of ``[s, e]``,
    as ``engine.<kind>``, or None."""
    best = None
    for kind, hs, he in _covering(sp, s, e):
        if 2 * (min(e, he) - max(s, hs)) > e - s and (
                best is None or he - hs < best[1]):
            best = (kind, he - hs)
    return PREFIX + best[0] if best else None


def idle_gaps(tr: trace.Trace, sp: Spans, top: int = 10) -> list:
    """``trace.breakdown``'s longest idle gaps, each labelled by the
    engine phase covering most of it, else by the harness annotation."""
    out = [[phase(sp, s, e) or trace.host_label(tr, s, e), e - s]
           for s, e in trace.gaps(tr)]
    return sorted(out, key=lambda g: -g[1])[:top]


def idle_by_phase(tr: trace.Trace, sp: Spans) -> dict:
    """The idle seconds between programs split by the innermost engine
    span at each instant (``engine.<kind>``), the rest by the harness
    annotation covering the gap."""
    out: dict = {}
    for s, e in trace.gaps(tr):
        spans_in = list(_covering(sp, s, e))
        cuts = sorted({s, e} | {t for _, hs, he in spans_in
                                for t in (hs, he) if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inner = [(he - hs, kind) for kind, hs, he in spans_in
                     if hs <= mid <= he]
            label = PREFIX + min(inner)[1] if inner \
                else trace.host_label(tr, s, e)
            out[label] = out.get(label, 0.0) + b - a
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def _by(sp: Spans, key, programs) -> dict:
    """Self seconds of the named programs' operations, summed by
    ``key(scope, op)``, averaged over the devices."""
    by: dict = {}
    for ops in sp.ops:
        mine = [op for op in ops if op[0] in programs]
        # each event under its own name: its seconds less those of the
        # events nested in it (a loop's body sits inside the loop's event)
        own = trace.self_times([(i, op[3], op[4])
                                for i, op in enumerate(mine)])
        for i, secs in own.items():
            k = key(mine[i][1], mine[i][2])
            if k is not None:
                by[k] = by.get(k, 0.0) + secs
    n = max(len(sp.ops), 1)
    return {k: v / n for k, v in sorted(by.items(), key=lambda kv: -kv[1])}


def device_scopes(sp: Spans, programs=DECODE) -> dict:
    """Device seconds of the named programs' operations by top-level
    model scope (``unscoped`` for the rest), by self time, averaged over
    the devices."""
    return _by(sp, lambda scope, op: scope, programs)


def scope_ops(sp: Spans, scope: str, programs=DECODE, top: int = 10) -> list:
    """The operations of one scope that took most self time."""
    by = _by(sp, lambda sc, op: trace.op_name(op) if sc == scope else None,
             programs)
    return [[k, v] for k, v in list(by.items())[:top]]


def per_step_ms(sp: Spans, scope: str, fused_iterations: int) -> "float | None":
    """Device ms of the decode programs' operations under ``scope`` per
    decode step executed (``engine.fused_iterations`` over the traced
    stretch, the divisor ``decode_step_ms`` uses); None without them."""
    if not sp.ops or not fused_iterations:
        return None
    secs = device_scopes(sp).get(scope)
    return None if secs is None else secs / fused_iterations * 1e3


def attention_ms_per_step(sp: Spans, fused_iterations: int) -> "float | None":
    return per_step_ms(sp, "attention", fused_iterations)


def pool_carry_ms_per_step(sp: Spans, fused_iterations: int) -> "float | None":
    """Device ms per decode step under no model scope: the layer scan's
    per-layer slices and write-backs of the stacked pool and weights and
    the compiler's whole-pool copies.  None where no operation carries a
    model scope (a program without named scopes)."""
    if not any(sc != UNSCOPED for ops in sp.ops for _, sc, _, _, _ in ops):
        return None
    return per_step_ms(sp, UNSCOPED, fused_iterations)


def host_ms_p50(events) -> "float | None":
    """Median over the engine's ``iteration`` spans of their duration
    less the ``sync`` spans inside them, in ms; None where the engine
    records no ``sync`` spans."""
    sync: dict = {}
    for e in events:
        if e["kind"] == "sync":
            sync[e["iteration"]] = sync.get(e["iteration"], 0.0) + e["dur"]
    if not sync:
        return None
    host = [e["dur"] - sync.get(e["iteration"], 0.0)
            for e in events if e["kind"] == "iteration"]
    return float(np.median(host)) * 1e3 if host else None
