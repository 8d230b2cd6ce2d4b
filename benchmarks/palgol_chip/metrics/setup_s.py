"""Seconds from process start to the first timed job: JAX start-up, the
graph build on the device, picking the roots, compiling or loading every
executable the jobs use."""


def read(record):
    return record["setup_s"]
