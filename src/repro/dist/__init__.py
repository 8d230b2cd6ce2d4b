"""Distribution layer: path-keyed sharding rules.

``repro.dist.sharding`` is the only module that names mesh axes for data
parallelism; everything else tags dimensions with its logical axes
(``ALL``, ``BATCH``) or asks it for param/batch shardings by family.
"""

from repro.dist import sharding  # noqa: F401

__all__ = ["sharding"]
