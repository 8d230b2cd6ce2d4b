"""Device self time of the collective operations in the traced job per
loop trip, in ms, averaged over the chips (``trace["kinds"]``): the
all-to-all, reduce-scatter, all-reduce, all-gather and
collective-permute ops, and their ``-start``/``-done`` halves. Nothing
where the trace holds none (one chip, or a CPU run)."""

_COLLECTIVES = ("all-to-all", "reduce-scatter", "all-reduce", "all-gather",
                "collective-permute")
KINDS = frozenset(f"{op}{half}" for op in _COLLECTIVES
                  for half in ("", "-start", "-done"))


def read(record):
    trace = record["trace"]
    ran = [j for j in record["jobs"] if "trips" in j]
    if trace is None or not ran or not sum(ran[0]["trips"]):
        return None
    seconds = sum(v for k, v in trace["kinds"].items() if k in KINDS)
    if not seconds:
        return None
    return 1000.0 * seconds / sum(ran[0]["trips"])
