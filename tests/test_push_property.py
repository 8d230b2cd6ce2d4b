"""Property tests for the executable push schedule + the byte-aware
``auto`` selector.

Invariants encoded:

* **push round structure** — for any randomized set of chain reads, the
  plan's push read rounds equal the PushSolver-minimal count the
  paper-faithful STM charges (``analyze_step(...).push_read_rounds()``),
  each round is one of the two push kinds carrying the combining op, and
  every request/reply *conversation* costs exactly ``2·hops`` supersteps:
  naive charges ``2·hops`` for ``hops = Σ (len(p)−1) + general reads``,
  a single-hop chain costs push exactly 2 (its one request + one combined
  reply), and deeper chains cost push at most ``2·hops`` (address flows
  overlap value flows — the paper's D⁴-in-3-rounds headline);
* **byte-aware auto never loses** — for randomized byte-cost models, the
  plan ``auto`` selects is never costlier than *both* pull and naive (nor
  push) under :func:`repro.core.plan.plan_score`.
"""

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import ast
from repro.core.analysis import analyze_step
from repro.core.plan import (
    ByteCostModel,
    ReadRound,
    lower_step,
    plan_score,
)

CHAIN_FIELDS = ["D", "E"]


def _chain_expr(pat):
    e = ast.Var("v")
    for f in pat:
        e = ast.FieldAccess(f, e)
    return e


def _step_reading(pats):
    """A synthetic step whose remote reads are exactly ``pats``."""
    body = tuple(
        ast.LocalWrite(f"X{i}", ":=", _chain_expr(p))
        for i, p in enumerate(pats)
    )
    return ast.Step("v", body)


@st.composite
def chain_patterns(draw):
    n = draw(st.integers(1, 3))
    pats = []
    for _ in range(n):
        k = draw(st.integers(2, 6))
        pats.append(
            tuple(draw(st.sampled_from(CHAIN_FIELDS)) for _ in range(k))
        )
    return pats


@settings(max_examples=40, deadline=None)
@given(chain_patterns())
def test_push_rounds_minimal_and_conversations_cost_two(pats):
    step = _step_reading(pats)
    info = analyze_step(step)
    push = lower_step(step, schedule="push")
    naive = lower_step(step, schedule="naive")
    # the executable plan charges exactly what the paper-faithful STM
    # counts (the re-alignment contract), via the two push round kinds
    assert push.read_rounds == info.push_read_rounds()
    for op in push.ops:
        if isinstance(op, ReadRound):
            assert op.kind in ("push_request", "push_reply")
            assert op.combiner == "min"
    # naive: every hop is one request + one reply — exactly 2·hops
    hops = sum(len(p) - 1 for p in info.read_patterns())
    assert naive.read_rounds == 2 * hops
    # push overlaps address and value flows: never more than naive,
    # and exactly 2·hops for a single-hop conversation
    assert push.read_rounds <= 2 * hops
    if len(pats) == 1 and len(pats[0]) == 2:
        assert push.read_rounds == 2
    # every schedule materializes the same requested patterns
    for p in info.read_patterns():
        assert p in push.materialized


@settings(max_examples=40, deadline=None)
@given(
    chain_patterns(),
    st.integers(1, 64),
    st.integers(1, 64),
    st.integers(0, 4096),
)
def test_byte_aware_auto_never_costlier_than_any_schedule(
    pats, request_set, combined, overhead
):
    step = _step_reading(pats)
    costs = ByteCostModel(
        n_vertices=64,
        request_set=request_set,
        combined_request_set=min(combined, request_set),
        superstep_overhead_bytes=overhead,
    )
    auto = lower_step(step, schedule="auto", byte_costs=costs)
    for sched in ("pull", "push", "naive"):
        hand = lower_step(step, schedule=sched)
        assert plan_score(auto, costs) <= plan_score(hand, costs), sched
    # and without costs the metric degrades to op count (ties → pull)
    bare = lower_step(step, schedule="auto")
    assert bare.n_supersteps == min(
        lower_step(step, schedule=s).n_supersteps
        for s in ("pull", "push", "naive")
    )
