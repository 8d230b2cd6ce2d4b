"""The trace reducer on hand-made events and on a recorded chip trace."""

from types import SimpleNamespace as E

import pytest

import trace_reduce as tr
from conftest import BENCH

DATA = BENCH / "tests" / "data"

HLO = """HloModule jit_fn

%fused_computation.2 (param_0: s32[8], param_1: s32[4]) -> s32[4] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %scatter.1 = s32[4]{0} scatter(s32[4]{0} %param_1, s32[8]{0} %param_0, s32[8]{0} %param_0), to_apply=%min
}

%fused_computation.3 (param_0: s32[4], param_1: s32[8]) -> s32[8] {
  ROOT %gather.1 = s32[8]{0} gather(s32[4]{0} %param_0, s32[8,1]{1,0} %param_1), offset_dims={}
}

ENTRY %main.9 (p: s32[4]) -> s32[4] {
  ROOT %fusion.3 = s32[8]{0} fusion(s32[4]{0} %p), kind=kCustom, calls=%fused_computation.3
}
"""


def test_fusions_are_named_by_what_they_call():
    bodies = tr.computations(HLO)
    assert set(bodies) == {"fused_computation.2", "fused_computation.3",
                           "main.9"}
    scatter = ("%fusion.12 = s32[4]{0:T(1024)} fusion(s32[8]{0} %a), "
               "kind=kCustom, calls=%fused_computation.2")
    gather = ("%fusion.13 = s32[8]{0:T(1024)} fusion(s32[4]{0} %b), "
              "kind=kCustom, calls=%fused_computation.3")
    loop = ("%select_fusion = s32[4]{0:T(1024)} fusion(pred[4]{0} %c), "
            "kind=kLoop, calls=%fused_computation.9")
    assert tr.op_kind(scatter, bodies) == "scatter"
    assert tr.op_kind(gather, bodies) == "gather"
    assert tr.op_kind(loop, bodies) == "fusion:kLoop"
    assert tr.op_kind(gather, {}) == "fusion:kCustom"
    assert tr.op_kind("%copy.2 = s32[4]{0:T(1024)S(1)} copy(s32[4]{0} %d)",
                      bodies) == "copy"
    w = ("%while.3 = (s32[4]{0:T(1024)}, pred[]{:T(512)}) while((s32[4]{0}, "
         "pred[]) %tuple.25), condition=%cond, body=%body")
    assert tr.op_kind(w, bodies) == "control"
    assert tr.op_name(scatter) == "fusion.12"


def test_nested_ops_are_charged_their_self_time():
    events = [E(name="while", start_ns=0, duration_ns=100),
              E(name="a", start_ns=10, duration_ns=30),
              E(name="b", start_ns=40, duration_ns=50),
              E(name="c", start_ns=50, duration_ns=10),
              E(name="d", start_ns=120, duration_ns=5)]
    got = {n: self_ns for n, _, _, self_ns in tr._self_times(events)}
    assert got == {"while": 20, "a": 30, "b": 40, "c": 10, "d": 5}
    assert tr._union([(0, 100), (10, 40), (120, 125), (125, 130)]) == [
        (0, 100), (120, 130)]


@pytest.fixture(scope="module")
def recorded():
    """One S-V job at Graph500 scale 12 on a TPU v5 lite, traced by the
    harness's spans, and the HLO text of its executable."""
    return tr.reduce(str(DATA / "sv12.xplane.pb"), "palgol.job",
                     (DATA / "sv12.hlo.txt").read_text())


def test_recorded_trace_reduces_to_fixed_numbers(recorded):
    same = lambda x: pytest.approx(x, rel=1e-12)  # noqa: E731
    assert recorded["window_s"] == same(0.01533828)
    assert recorded["busy_s"] == same(0.011499254)
    kinds = recorded["kinds"]
    assert kinds["gather"] == same(0.005949889)
    assert kinds["scatter"] == same(0.005542487)
    # the segment min of the neighbour reduction: a scatter held by a
    # fusion inside the fusion the trace names
    assert recorded["device_ops"][0] == ["fusion.23 scatter", same(0.005401065)]
    assert len(recorded["device_ops"]) == 10
    assert sum(s for _, s in recorded["idle_gaps"]) <= (
        recorded["window_s"] - recorded["busy_s"] + 1e-12)


def test_the_job_span_must_be_in_the_trace():
    with pytest.raises(ValueError):
        tr.reduce(str(DATA / "sv12.xplane.pb"), "no.such.span")
