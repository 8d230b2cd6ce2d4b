"""STM merging/fusion unit tests."""

from repro.core.parser import parse
from repro.core.stm import build_stm, superstep_report


SIMPLE = """
for v in V
    local A[v] := 0
end
for v in V
    local A[v] := A[v] + 1
end
"""

NBR_ITER = """
for v in V
    local A[v] := Id[v]
end
do
    for v in V
        let m = minimum [A[e.id] | e <- Nbr[v]]
        if (m < A[v])
            local A[v] := m
    end
until fix [A]
"""

CHAIN_RW = """
do
    for v in V
        if (A[A[v]] == A[v])
            remote A[A[v]] <?= Id[v]
    end
until fix [A]
"""


class TestStmOptimizations:
    def test_sequence_merging_saves_one(self):
        prog = parse(SIMPLE)
        _, opt = build_stm(prog, "push", optimize=True)
        _, naive = build_stm(prog, "naive", optimize=False)
        assert opt.base == naive.base - 1  # two MAIN states merged into one

    def test_iteration_fusion_removes_send_superstep(self):
        prog = parse(NBR_ITER)
        _, fused = build_stm(prog, "push", optimize=True)
        _, plain = build_stm(prog, "push", optimize=False)
        # body = [RR(send), MAIN]: fused per-iter = 1, unfused = 2
        assert fused.per_iter[0] == 1
        assert plain.per_iter[0] == 2

    def test_chain_and_remote_write_states(self):
        prog = parse(CHAIN_RW)
        _, push = build_stm(prog, "push", optimize=True)
        _, pull = build_stm(prog, "pull", optimize=True)
        _, naive = build_stm(prog, "naive", optimize=False)
        # push: D² chain = 2 RR + MAIN + RU, fused ⇒ 3/iter
        assert push.per_iter[0] == 3
        # pull: 1 RR + MAIN + RU, fused ⇒ 2/iter
        assert pull.per_iter[0] == 2
        # naive: 2 RR (request/reply) + MAIN + RU, unfused ⇒ 4/iter
        assert naive.per_iter[0] == 4

    def test_report_orderings_on_stdlib(self):
        from repro.core import algorithms as alg

        for name, src in alg.ALL.items():
            rep = superstep_report(parse(src))
            trips = {i: 3 for i in range(4)}
            assert (
                rep["palgol_pull"].count(trips)
                <= rep["palgol_push"].count(trips)
                <= rep["naive"].count(trips)
            ), name
