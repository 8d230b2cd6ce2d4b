"""MB per chip that the traced job's collectives carried per loop trip,
as their operands' static shapes have them (padded): the program's own
count (``PartitionedProgram``'s ``comm_bytes``, every ``<primitive>/padded``
entry), over the trips. Nothing where the placement keeps no such count."""


def read(record):
    ran = [j for j in record["jobs"]
           if "comm_bytes" in j.get("counts", {}) and sum(j["trips"])]
    if not ran:
        return None
    j = ran[0]
    padded = sum(v for k, v in j["counts"]["comm_bytes"].items()
                 if k.endswith("/padded"))
    return padded / 1e6 / sum(j["trips"])
