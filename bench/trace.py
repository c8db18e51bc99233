"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device program executions, device operations, and the harness's
own host annotations, all in seconds on the trace's clock.

A device plane is one named ``/device:<accelerator>:<n>``.  On it, the
line whose name holds "Modules" lists one event per program execution
(``jit_run``, ``jit_run_chunk``, ...), and the line "XLA Ops" one event
per operation.  Host annotations are the ``jax.profiler.TraceAnnotation``
spans the harness puts around its calls into the engine.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

ANNOTATIONS = ("client.submit", "engine.step", "client.drain", "client.idle")
_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(name: str) -> str:
    """``jit_run(1234)`` -> ``jit_run``."""
    return _SUFFIX.sub("", name.strip())


@dataclass
class Trace:
    #: per device: [(program, start_s, end_s)], sorted by start
    programs: "list[list[tuple[str, float, float]]]" = field(
        default_factory=list)
    #: per device: [(op, start_s, end_s)]
    ops: "list[list[tuple[str, float, float]]]" = field(default_factory=list)
    #: [(annotation, start_s, end_s)]
    host: "list[tuple[str, float, float]]" = field(default_factory=list)
    #: length of the traced window, by the host's clock
    window_s: float = 0.0

    @property
    def devices(self) -> int:
        return len(self.programs)


def load(path, window_s: float) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    tr = Trace(window_s=window_s)
    for plane in pd.planes:
        if plane.name.startswith("/device:") and ":CPU" not in plane.name:
            progs, ops = [], []
            for line in plane.lines:
                if "Modules" in line.name:
                    progs += [(program_name(e.name), e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9)
                              for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
            if progs or ops:
                tr.programs.append(sorted(progs, key=lambda e: e[1]))
                tr.ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host += [(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events if e.name in ANNOTATIONS]
    tr.host.sort(key=lambda e: e[1])
    return tr


def union_s(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices
    (program executions where a device reports no operations)."""
    if not tr.devices:
        return 0.0
    per = []
    for progs, ops in zip(tr.programs, tr.ops):
        src = ops if ops else progs
        per.append(union_s([(s, e) for _, s, e in src]))
    return float(np.mean(per))


def program_time(tr: Trace, names) -> "tuple[float, int]":
    """(device seconds, executions) of the named programs, summed over
    the devices."""
    names = set(names)
    total, count = 0.0, 0
    for progs in tr.programs:
        for name, s, e in progs:
            if name in names:
                total += e - s
                count += 1
    return total, count


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def gaps(tr: Trace, device: int = 0, names=None) -> "list[tuple[float, float]]":
    """Idle stretches between consecutive programs on ``device`` (only
    the ``names`` programs, where given)."""
    if tr.devices <= device:
        return []
    progs = [p for p in tr.programs[device] if names is None or p[0] in names]
    out, end = [], None
    for _, s, e in progs:
        if end is not None and s > end:
            out.append((end, s))
        end = e if end is None else max(end, e)
    return out


def host_label(tr: Trace, s: float, e: float) -> str:
    """The host annotation that covers most of ``[s, e]``, or "none"."""
    best, label = 0.0, "none"
    for name, hs, he in tr.host:
        if hs > e:
            break
        ov = _overlap(s, e, hs, he)
        if ov > best:
            best, label = ov, name
    return label


def live_gaps(tr: Trace, names=None, device: int = 0) -> "list[float]":
    """Gaps between programs while requests are live: those the harness
    did not spend idle waiting for an arrival."""
    return [e - s for s, e in gaps(tr, device, names)
            if host_label(tr, s, e) != "client.idle"]


def op_name(text: str) -> str:
    """``%copy.55 = bf16[32,273,16,32,80]{...} copy(...)`` ->
    ``copy.55 bf16[32,273,16,32,80]``: the instruction and its result."""
    lhs, _, rhs = text.partition(" = ")
    shape = "(tuple)" if rhs.startswith("(") else rhs.split("{")[0].split(" ")[0]
    return f"{lhs.lstrip('%')} {shape}".strip()[:120]


def self_times(events) -> dict:
    """Seconds of each named event not covered by the events nested in
    it (a loop's body operations sit inside the loop's own event)."""
    out: dict = {}
    stack: list = []            # [name, end, child seconds]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, end, kids, start = stack.pop()
            out[n] = out.get(n, 0.0) + (end - start) - kids
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, s])
    while stack:
        n, end, kids, start = stack.pop()
        out[n] = out.get(n, 0.0) + (end - start) - kids
    return out


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most time (self time, averaged
    over the devices), and the longest idle gaps by what the host was
    doing in them."""
    by_op: dict = {}
    for ops, progs in zip(tr.ops, tr.programs):
        src = [(op_name(n), s, e) for n, s, e in ops] if ops else progs
        for name, secs in self_times(src).items():
            by_op[name] = by_op.get(name, 0.0) + secs
    n = max(tr.devices, 1)
    ops = sorted(([k, v / n] for k, v in by_op.items()),
                 key=lambda kv: -kv[1])[:top]
    idle = sorted(([host_label(tr, s, e), e - s] for s, e in gaps(tr)),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": ops, "idle_gaps": idle}
