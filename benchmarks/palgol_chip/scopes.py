"""Device time of one traced job by Palgol plan item.

The program names its device work after the Palgol program
(``repro.core.codegen``): ``jax.named_scope`` puts each op's path in the
HLO ``op_name`` metadata, for example
``jit(fn)/palgol/L0/while/body/s1/local/nbr/jit(_take)/gather``. This
module reads that path back, with nothing of the program imported:

* the plan item of an op is ``palgol``, the ``L<i>`` of each loop around
  it, its step ``s<sidx>`` and its innermost leaf (``chain``, ``nbr``,
  ``remote``, ``local``, ``stop``, or a loop's ``fixpoint``), here
  ``palgol/L0/s1/nbr``; JAX's own ``while``/``body``/``cond`` are
  skipped, and the path ends at the first other component (a jitted
  helper, the primitive). A loop's ``while`` op itself is ``palgol/L0``;
* each op of the trace's ``XLA Ops`` line is charged its self time (as
  ``trace_reduce`` charges it) to the plan item of the HLO instruction it
  names, or to :data:`UNSCOPED` where that instruction carries no
  ``palgol`` path (XLA's own copies, or a program that names nothing).

Busy time is ``trace_reduce``'s, from the same trace and window.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

import trace_reduce

#: the bucket of device time under no ``palgol`` path
UNSCOPED = "unscoped"
LEAVES = ("chain", "nbr", "remote", "local", "stop", "fixpoint")
_JAX_LOOP = ("while", "body", "cond")
_METADATA = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"'
)


def plan_item(op_name: str) -> Optional[str]:
    """The plan item an HLO ``op_name`` names, or ``None``."""
    parts = op_name.split("/")
    if "palgol" not in parts:
        return None
    path, step, leaf = ["palgol"], None, None
    for p in parts[parts.index("palgol") + 1:]:
        if re.fullmatch(r"L\d+", p) and step is None and leaf is None:
            path.append(p)
        elif re.fullmatch(r"s\d+", p) and step is None and leaf is None:
            step = p
        elif p in LEAVES:
            leaf = p
        elif p not in _JAX_LOOP:
            break
    return "/".join(path + [x for x in (step, leaf) if x])


def op_names(hlo_text: str) -> Dict[str, str]:
    """``instruction name -> op_name`` of every instruction of an HLO
    module's text that carries one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _METADATA.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def reduce(path: str, job_span: str, hlo_text: str) -> dict:
    """``scopes`` (device self seconds per plan item, with
    :data:`UNSCOPED`), ``busy_s`` and ``window_s`` of the job in the
    trace at ``path``; times averaged over the devices that ran ops."""
    from jax.profiler import ProfileData

    names = op_names(hlo_text)
    planes = list(ProfileData.from_file(path).planes)
    window, _ = trace_reduce._host_spans(planes, job_span)
    scopes: Dict[str, float] = {}
    chips = 0
    for plane in planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            charged = charge(line.events, names, window)
            chips += bool(charged)
            for k, v in charged.items():
                scopes[k] = scopes.get(k, 0.0) + v
    summary = trace_reduce.reduce(path, job_span, hlo_text)
    chips = max(chips, 1)
    return {
        "scopes": {k: v / chips for k, v in scopes.items()},
        "busy_s": summary["busy_s"],
        "window_s": summary["window_s"],
    }


def charge(events, names: Dict[str, str], window) -> Dict[str, float]:
    """Self seconds per plan item of the ops of one ``XLA Ops`` line that
    overlap ``window`` (start and end in ns); ``names`` is
    :func:`op_names` of the executable."""
    w0, w1 = window
    out: Dict[str, float] = {}
    for name, s, e, self_ns in trace_reduce._self_times(events):
        if e > w0 and s < w1:
            op = names.get(trace_reduce.op_name(name), "")
            item = plan_item(op) or UNSCOPED
            out[item] = out.get(item, 0.0) + self_ns / 1e9
    return out


def leaf_ms_per_trip(scopes: Dict[str, float], leaf: str, trips: int):
    """Device ms per loop trip of the plan items inside a loop whose leaf
    is ``leaf``; ``None`` where there are none or no trips."""
    s = sum(v for k, v in scopes.items()
            if _in_loop(k) and k.rsplit("/", 1)[-1] == leaf)
    return 1000.0 * s / trips if s and trips else None


def outside_loops_s(scopes: Dict[str, float]):
    """Device seconds of the plan items outside every loop (the steps
    before a loop and the prefetch loop fusion puts in front of it)."""
    s = sum(v for k, v in scopes.items()
            if k != UNSCOPED and not _in_loop(k))
    return s or None


def top(scopes: Dict[str, float], n: int = 10) -> List[list]:
    """The ``n`` largest plan items as ``[item, seconds]``."""
    return [[k, v] for k, v in
            sorted(scopes.items(), key=lambda kv: -kv[1])[:n]]


def _in_loop(item: str) -> bool:
    return bool(re.match(r"palgol/L\d+", item))
