"""Device time of scatter operations in the traced job, per loop trip, in
ms: the segment reductions over edges and the remote writes."""

from metrics_common import per_trip_ms


def read(record):
    return per_trip_ms(record, "scatter")
