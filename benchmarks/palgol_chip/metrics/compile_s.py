"""Seconds JAX spent obtaining executables during set-up, compiled or
loaded from the persistent cache (its backend-compile events)."""


def read(record):
    return record["compile_s"]
