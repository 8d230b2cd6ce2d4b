"""Essential bytes of a Palgol job's edge passes, for ``edge_pass_roofline``.

The count is a lower bound on what any dense implementation of the job
must move between HBM and the cores. For every execution of a step that
reduces over edges (a comprehension or loop over ``In``, ``Out`` or
``Nbr``), it counts, per edge pass:

* 4 B per live edge for the neighbour id;
* 4 B more per live edge where the pass reads the edge weight (``e.w``);
* 4 B per vertex, plus 4, for the destination as a CSR offset;

and, once per execution of the step, each field the step reads or writes
at its own width per vertex. Gathered values (``D[e.id]``), the edge mask
and the padding slots are not counted, so a faster kernel cannot push the
share past 100%. A change that skips edges by frontier moves fewer bytes
than this counts, and needs the count redone first.

The steps are read from the Palgol source by this module, not from the
program's plan, so that a change to the planner cannot change the count.
Steps inside the ``i``-th ``do ... until`` (in source order) run once per
trip of loop ``i``; steps outside any loop run once. The peak the share is
taken against is in ``peaks.json``, with its source.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence

_EDGE_PASS = re.compile(r"\b([A-Za-z_]\w*)\s*<-\s*(In|Out|Nbr)\s*\[")
_FIELD = re.compile(r"\b([A-Z]\w*)\s*\[")
_NOT_FIELDS = {"In", "Out", "Nbr", "Id"}


@dataclasses.dataclass(frozen=True)
class Step:
    loop: Optional[int]  # index of the innermost enclosing loop, or None
    weighted_passes: int  # edge passes that read the weight
    plain_passes: int  # edge passes that do not
    fields: frozenset


def steps(text: str) -> List[Step]:
    """The steps of a Palgol program with their edge passes and fields."""
    out: List[Step] = []
    loops: List[int] = []
    n_loops = 0
    body: Optional[List[str]] = None
    for raw in text.splitlines():
        line = raw.split("#", 1)[0].strip()
        if not line:
            continue
        word = line.split()[0]
        if body is None and word == "for" and re.search(r"\bin\s+V\b", line):
            body = []
        elif body is not None and line == "end":
            out.append(_step("\n".join(body), loops[-1] if loops else None))
            body = None
        elif body is not None:
            body.append(line)
        elif word == "do":
            loops.append(n_loops)
            n_loops += 1
        elif word == "until":
            loops.pop()
        elif word == "stop":
            out.append(_step(line, loops[-1] if loops else None))
    return out


def _step(body: str, loop: Optional[int]) -> Step:
    weighted = plain = 0
    for m in _EDGE_PASS.finditer(body):
        if re.search(rf"\b{re.escape(m.group(1))}\.w\b", body):
            weighted += 1
        else:
            plain += 1
    fields = frozenset(_FIELD.findall(body)) - _NOT_FIELDS
    return Step(loop, weighted, plain, fields)


def job_bytes(
    text: str,
    trips: Sequence[int],
    n_vertices: int,
    live_edges: int,
    itemsize: Dict[str, int],
) -> int:
    """Essential bytes of one job of ``text`` that ran ``trips[i]`` trips
    of its ``i``-th loop, on a graph of ``n_vertices`` and ``live_edges``
    directed edges; ``itemsize`` maps each field to its bytes per vertex."""
    total = 0
    for s in steps(text):
        passes = s.weighted_passes + s.plain_passes
        if not passes:
            continue
        once = (
            passes * (4 * live_edges + 4 * (n_vertices + 1))
            + s.weighted_passes * 4 * live_edges
            + n_vertices * sum(itemsize[f] for f in s.fields)
        )
        total += once * (1 if s.loop is None else trips[s.loop])
    return total
