"""Device time by plan item, on hand-made events and on recorded chip
traces."""

from types import SimpleNamespace as E

import pytest

import scopes
from conftest import BENCH

DATA = BENCH / "tests" / "data"

HLO = """HloModule jit_fn

ENTRY %main.9 (p: s32[4]) -> s32[4] {
  %fusion.1 = s32[8]{0} fusion(s32[4]{0} %p), kind=kCustom, calls=%f.1, metadata={op_name="jit(fn)/palgol/s1/nbr/jit(_take)/gather" stack_frame_id=3}
  %while.2 = (s32[8]{0}) while((s32[8]{0}) %t), condition=%c, body=%b, metadata={op_name="jit(fn)/palgol/L0/while" stack_frame_id=3}
  %fusion.3 = s32[8]{0} fusion(s32[4]{0} %p), kind=kCustom, calls=%f.3, metadata={op_name="jit(fn)/palgol/L0/while/body/s1/local/nbr/jit(_where)/select_n" stack_frame_id=3}
  %fusion.4 = s32[4]{0} fusion(s32[8]{0} %q), kind=kLoop, calls=%f.4, metadata={op_name="jit(fn)/palgol/L0/while/body/s1/local/gt" stack_frame_id=3}
  %copy-done.5 = s32[4]{0} copy-done(%copy-start.5)
  ROOT %fusion.6 = s32[4]{0} fusion(s32[8]{0} %q), kind=kLoop, calls=%f.6, metadata={op_name="jit(fn)/palgol/L0/while/cond/fixpoint/lt" stack_frame_id=3}
}
"""


@pytest.mark.parametrize("op_name,item", [
    ("jit(fn)/palgol/L0/while/body/s1/local/nbr/jit(_take)/gather",
     "palgol/L0/s1/nbr"),
    ("jit(fn)/palgol/L0/while/body/s1/chain/jit(_take)/gather",
     "palgol/L0/s1/chain"),
    ("jit(fn)/palgol/L0/while/body/fixpoint/add", "palgol/L0/fixpoint"),
    ("jit(fn)/palgol/L0/while/cond/fixpoint/lt", "palgol/L0/fixpoint"),
    ("jit(fn)/palgol/L0/while", "palgol/L0"),
    ("jit(fn)/palgol/s0/local/jit(_where)/select_n", "palgol/s0/local"),
    ("jit(fn)/palgol/broadcast_in_dim", "palgol"),
    ("jit(fn)/palgol/L0/while/body/L1/while/body/s2/remote/scatter-min",
     "palgol/L0/L1/s2/remote"),
    ("jit(ss_fn)/shard_map/palgol/L0/s3/stop/or", "palgol/L0/s3/stop"),
    ("jit(fn)/while/body/jit(_take)/gather", None),
    ("graph.src", None),
])
def test_an_op_name_is_read_back_to_its_plan_item(op_name, item):
    assert scopes.plan_item(op_name) == item


def test_ops_are_charged_to_their_plan_items_or_to_unscoped():
    names = scopes.op_names(HLO)
    assert names["while.2"] == "jit(fn)/palgol/L0/while"
    assert "copy-done.5" not in names
    events = [
        E(name="%fusion.1 = s32[8]{0} fusion(%p)", start_ns=0, duration_ns=10),
        E(name="%while.2 = (s32[8]{0}) while(%t)", start_ns=10,
          duration_ns=100),
        E(name="%fusion.3 = s32[8]{0} fusion(%p)", start_ns=20, duration_ns=40),
        E(name="%fusion.4 = s32[4]{0} fusion(%q)", start_ns=60, duration_ns=20),
        E(name="%copy-done.5 = s32[4]{0} copy-done(%c)", start_ns=80,
          duration_ns=5),
        E(name="%fusion.6 = s32[4]{0} fusion(%q)", start_ns=85, duration_ns=5),
        E(name="%fusion.1 = s32[8]{0} fusion(%p)", start_ns=200,
          duration_ns=10),  # after the window
    ]
    got = scopes.charge(events, names, (0, 150))
    assert got == pytest.approx({
        "palgol/s1/nbr": 10e-9, "palgol/L0": 30e-9, "palgol/L0/s1/nbr": 40e-9,
        "palgol/L0/s1/local": 20e-9, scopes.UNSCOPED: 5e-9,
        "palgol/L0/fixpoint": 5e-9,
    }, rel=1e-12)
    assert scopes.leaf_ms_per_trip(got, "nbr", 2) == pytest.approx(2e-5)
    assert scopes.leaf_ms_per_trip(got, "chain", 2) is None
    assert scopes.outside_loops_s(got) == pytest.approx(10e-9)
    assert scopes.top(got, 2)[0] == ["palgol/L0/s1/nbr", pytest.approx(4e-8)]


def test_a_trace_of_an_unnamed_program_is_all_unscoped():
    """The PR 7 recording: its program named no plan item."""
    got = scopes.reduce(str(DATA / "sv12.xplane.pb"), "palgol.job",
                        (DATA / "sv12.hlo.txt").read_text())
    assert set(got["scopes"]) == {scopes.UNSCOPED}
    # self times sum to the busy time: one op at a time on the line
    assert got["scopes"][scopes.UNSCOPED] == pytest.approx(
        got["busy_s"], rel=5e-3)


@pytest.fixture(scope="module")
def recorded():
    """One S-V job at Graph500 scale 12 (seed 3100000012, 5 trips) on a
    TPU v5 lite, traced by ``scope_report.py``, and its executable's HLO
    text, whose ops the program named."""
    return scopes.reduce(str(DATA / "sv12_scoped.xplane.pb"), "palgol.job",
                         (DATA / "sv12_scoped.hlo.txt").read_text())


def test_recorded_scoped_trace_reduces_to_fixed_numbers(recorded):
    same = lambda x: pytest.approx(x, rel=1e-12)  # noqa: E731
    got = recorded["scopes"]
    assert recorded["busy_s"] == same(0.011501916)
    assert got == {
        "palgol/L0/s1/nbr": same(0.010073077),
        "palgol/s1/nbr": same(0.00093488),
        "palgol/L0/s1/remote": same(0.000307523),
        "palgol/L0/s1/chain": same(0.00014598),
        "palgol/s1/chain": same(2.9588e-05),
        "palgol/L0/fixpoint": same(7.929e-06),
        "palgol/L0": same(2.008e-06),
        scopes.UNSCOPED: same(8.68e-07),
        "palgol/L0/s1/local": same(5.6e-08),
        "palgol": same(7e-09),
    }
    # every op is charged once: the items and the unscoped bucket sum to
    # the busy time, and the unscoped part is XLA's copies
    assert sum(got.values()) == pytest.approx(recorded["busy_s"], rel=1e-6)
    assert got[scopes.UNSCOPED] < 1e-4 * recorded["busy_s"]
    assert scopes.leaf_ms_per_trip(got, "nbr", 5) == pytest.approx(2.0146154)
    assert scopes.outside_loops_s(got) == pytest.approx(0.000964475)
