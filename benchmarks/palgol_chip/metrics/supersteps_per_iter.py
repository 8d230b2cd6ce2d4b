"""Fused supersteps the plan dispatched per loop trip, from the program's
own count (``CompiledProgram.run``'s ``fused_pull``) over the trips."""


def read(record):
    ran = [j for j in record["jobs"] if "counts" in j and sum(j["trips"])]
    if not ran:
        return None
    j = ran[0]
    return j["counts"]["fused_pull"] / sum(j["trips"])
