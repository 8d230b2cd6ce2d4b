"""Reduce a profiler trace of one job to device time by operation kind,
device busy time, and the host's activity in the device's idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes. What
is read from it:

* the job's window: the host span named ``job_span`` (a
  ``jax.profiler.TraceAnnotation`` around the job);
* the device's operations: the ``XLA Ops`` line of each ``/device:TPU:<k>``
  plane. Operations nest there (a ``while`` holds its body's operations),
  so each operation is charged its self time: its duration less that of
  the operations it holds;
* what each operation is: the HLO opcode the trace names. A fusion is
  named after what it calls, so the executable's HLO text, where given,
  says what the fused computation does: a fusion that holds a ``scatter``,
  itself or in a fusion it calls, is a scatter, one that holds a
  ``gather`` a gather, else ``fusion`` with its fusion kind;
* the host's activity: the innermost host span, on the thread that ran
  the job, around the middle of each idle gap.

Busy time is the union of the operations' intervals inside the window,
averaged over the devices that ran any.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_FUSION_KIND = re.compile(r"kind=(k\w+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INNER_KINDS = ("scatter", "gather", "sort")
#: operations that only hold others; their self time is loop control
_CONTAINERS = ("while", "conditional", "call")


def computations(hlo_text: str) -> Dict[str, str]:
    """``name -> body`` of every computation in an HLO module's text."""
    out: Dict[str, str] = {}
    name: Optional[str] = None
    body: List[str] = []
    for line in hlo_text.splitlines():
        if name is None:
            m = _COMPUTATION.match(line)
            if m:
                name, body = m.group(1), []
        elif line.strip() == "}":
            out[name] = "\n".join(body)
            name = None
        else:
            body.append(line)
    return out


def op_name(event_name: str) -> str:
    """``%fusion.12 = s32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_kind(event_name: str, bodies: Dict[str, str]) -> str:
    """What an ``XLA Ops`` event does, by the HLO it names."""
    rest = event_name.split(" = ", 1)[-1]
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else op_name(event_name)
    if opcode in _CONTAINERS:
        return "control"
    if opcode != "fusion":
        return opcode
    calls = _CALLS.search(rest)
    kind = _held_kind(calls.group(1), bodies, set()) if calls else None
    if kind:
        return kind
    fk = _FUSION_KIND.search(rest)
    return f"fusion:{fk.group(1)}" if fk else "fusion"


def _held_kind(name: str, bodies: Dict[str, str], seen: set):
    """The first of :data:`_INNER_KINDS` that computation ``name`` holds,
    itself or in the fusions it calls."""
    body = bodies.get(name, "")
    for kind in _INNER_KINDS:
        if re.search(rf" {kind}\(", body):
            return kind
    for callee in _CALLS.findall(body):
        if callee not in seen:
            seen.add(callee)
            kind = _held_kind(callee, bodies, seen)
            if kind:
                return kind
    return None


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _self_times(events) -> List[Tuple[str, int, int, int]]:
    """``(name, start, end, self ns)`` of nested events on one line."""
    evs = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in events), key=lambda x: (x[0], -x[1]))
    out = []
    stack: List[int] = []  # indices into out of the open events
    for s, e, name in evs:
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = out[stack[-1]]
            out[stack[-1]] = (p[0], p[1], p[2], p[3] - (e - s))
        out.append((name, s, e, e - s))
        stack.append(len(out) - 1)
    return out


def _host_spans(planes, job_span: str):
    """The job's window and the spans on the thread that ran it."""
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            for e in events:
                if e.name == job_span:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                    spans = [(x.start_ns, x.start_ns + x.duration_ns, x.name)
                             for x in events]
                    return window, spans
    raise ValueError(f"no host span {job_span!r} in the trace")


def reduce(path: str, job_span: str, hlo_text: str = "") -> dict:
    """``busy_s``, ``window_s``, ``kinds`` (device self seconds by kind),
    and the ``breakdown`` lists ``device_ops`` and ``idle_gaps`` (ten
    each, largest first); every time but the window is averaged over the
    devices that ran operations."""
    from jax.profiler import ProfileData

    bodies = computations(hlo_text)
    planes = list(ProfileData.from_file(path).planes)
    (w0, w1), spans = _host_spans(planes, job_span)
    kinds: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    busy: List[float] = []
    for plane in planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            timed = [t for t in _self_times(line.events)
                     if t[2] > w0 and t[1] < w1]
            if not timed:
                continue
            for name, s, e, self_ns in timed:
                kind = op_kind(name, bodies)
                sec = self_ns / 1e9
                kinds[kind] = kinds.get(kind, 0.0) + sec
                label = f"{op_name(name)} {kind}"
                ops[label] = ops.get(label, 0.0) + sec
            held = _union([(max(s, w0), min(e, w1))
                           for _, s, e, _ in timed])
            busy.append(sum(e - s for s, e in held) / 1e9)
            edges = [w0] + [x for iv in held for x in iv] + [w1]
            for g0, g1 in zip(edges[::2], edges[1::2]):
                if g1 > g0:
                    label = _activity(spans, (g0 + g1) / 2)
                    gaps[label] = gaps.get(label, 0.0) + (g1 - g0) / 1e9
    chips = max(len(busy), 1)
    top = lambda d: [[k, v / chips] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": sum(busy) / chips,
        "window_s": (w1 - w0) / 1e9,
        "kinds": {k: v / chips for k, v in kinds.items()},
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
    }


def _activity(spans, t: float) -> str:
    """The innermost host span around time ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "host idle"
