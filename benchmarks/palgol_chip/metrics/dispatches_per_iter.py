"""``shard_map`` dispatches the traced job made per loop trip: the
program's own count (``BSPResult.supersteps``, one per dispatched
superstep), over the trips. Nothing where the placement keeps no such
count."""


def read(record):
    ran = [j for j in record["jobs"]
           if "dispatches" in j.get("counts", {}) and sum(j["trips"])]
    if not ran:
        return None
    j = ran[0]
    return j["counts"]["dispatches"] / sum(j["trips"])
