"""The CSV row format of ``benchmarks/run.py``."""

from __future__ import annotations


def row(name: str, us: float, derived: str = "") -> str:
    return f"{name},{us:.1f},{derived}"
