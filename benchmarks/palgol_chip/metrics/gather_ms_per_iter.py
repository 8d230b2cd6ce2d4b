"""Device time of gather operations in the traced job, per loop trip, in
ms: neighbour reads over edges and chain access."""

from metrics_common import per_trip_ms


def read(record):
    return per_trip_ms(record, "gather")
