"""The traced job's essential edge-pass bytes (``edge_bytes.job_bytes``)
over what the chips' peak HBM bandwidth moves in the job's per-chip
device busy time, in %: chips × peak bytes/s × busy seconds, busy time
being averaged over the chips."""

import edge_bytes


def read(record):
    trace = record["trace"]
    ran = [j for j in record["jobs"]
           if "n_shards" in j.get("counts", {})]
    if trace is None or not ran or trace["busy_s"] <= 0:
        return None
    j = ran[0]
    moved = edge_bytes.job_bytes(
        record["program"], j["trips"], record["n_vertices"],
        record["live_edges"], j["itemsize"],
    )
    if not moved:
        return None
    chips = j["counts"]["n_shards"]
    peak = record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * moved / (chips * peak * trace["busy_s"])
