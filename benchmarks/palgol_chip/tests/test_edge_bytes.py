"""The essential-bytes count of ``edge_pass_roofline``, by hand."""

import edge_bytes
from conftest import BENCH

N, E = 10, 40


def read(name):
    return (BENCH / "programs" / f"{name}.palgol").read_text()


def test_wcc_counts_ids_offsets_and_its_one_field():
    text = read("wcc")
    # per trip: 4 B/edge id + 4 B/vertex offsets (+4) + C at 4 B/vertex
    per_trip = 4 * E + 4 * (N + 1) + 4 * N
    assert edge_bytes.job_bytes(text, [5], N, E, {"C": 4}) == 5 * per_trip


def test_sssp_counts_the_weight_and_both_fields_at_their_width():
    text = read("sssp_rooted")
    # the init step has no edge pass; the loop step reads e.w, D and A
    per_trip = 8 * E + 4 * (N + 1) + N * (4 + 1)
    assert edge_bytes.job_bytes(
        text, [7], N, E, {"D": 4, "A": 1, "Root": 1}
    ) == 7 * per_trip


def test_sv_chain_reads_and_remote_writes_add_no_edge_bytes():
    text = read("sv")
    per_trip = 4 * E + 4 * (N + 1) + 4 * N
    assert edge_bytes.job_bytes(text, [6], N, E, {"D": 4}) == 6 * per_trip


def test_steps_outside_loops_run_once_and_loops_count_their_own_trips():
    text = """
for v in V
    local Deg[v] := count [1 | e <- Out[v]]
end
do
    for v in V
        let s = sum [P[x.id] * x.w | x <- In[v]]
        let t = minimum [Q[y.id] | y <- Nbr[v]]
        local P[v] := s + t  # Q is read by the pass above
    end
until fix [P]
do
    for v in V
        local Q[v] := Q[v] + 1.0
    end
until iter [3]
"""
    st = edge_bytes.steps(text)
    assert [(s.loop, s.weighted_passes, s.plain_passes) for s in st] == [
        (None, 0, 1), (0, 1, 1), (1, 0, 0)]
    assert st[1].fields == {"P", "Q"}
    size = {"Deg": 4, "P": 4, "Q": 4}
    first = 4 * E + 4 * (N + 1) + 4 * N
    loop = (4 * E + 4 * (N + 1)) * 2 + 4 * E + 8 * N
    assert edge_bytes.job_bytes(text, [9, 3], N, E, size) == first + 9 * loop
