"""Arithmetic the per-layer metric readers share."""


def per_trip_ms(record, kind: str):
    """Device ms of ops of ``kind`` in the traced job per loop trip, or
    ``None`` where the trace has none."""
    trace = record["trace"]
    ran = [j for j in record["jobs"] if "trips" in j]
    if trace is None or not ran or not sum(ran[0]["trips"]):
        return None
    seconds = trace["kinds"].get(kind)
    if not seconds:
        return None
    return 1000.0 * seconds / sum(ran[0]["trips"])
