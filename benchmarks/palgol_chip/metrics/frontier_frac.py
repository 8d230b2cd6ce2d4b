"""Share of the traced job's vertex visits that found a vertex changed:
the vertices whose fix fields changed, summed over every trip of every
loop (the program's own count, ``CompiledProgram.run``'s
``counts["active_sets"]``), over vertices × loop trips. Each trip passes
over every edge whatever this share; a frontier-compacted edge pass would
pass over the changed vertices' edges alone. Nothing where the program
keeps no frontier count, or where the trace holds no device op (a CPU
run)."""


def read(record):
    trace = record["trace"]
    ran = [j for j in record["jobs"] if "counts" in j]
    if trace is None or trace["busy_s"] <= 0 or not ran:
        return None
    j = ran[0]
    sets = j["counts"].get("active_sets")
    trips = sum(j["trips"])
    if sets is None or not trips:
        return None
    return sum(map(sum, sets)) / (record["n_vertices"] * trips)
