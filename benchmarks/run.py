"""Paper Table 5: superstep counts under the three compilers.

    PYTHONPATH=src python -m benchmarks.run [--scale 10]

Prints ``name,us_per_call,derived`` CSV (the counts are in ``derived``).
Times are measured on the chip, by ``benchmarks/palgol_chip/run.py``.
"""

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--scale", type=int, default=10, help="log2 graph size (default 2^10)"
    )
    args = ap.parse_args()

    from repro import compile_cache

    compile_cache.enable()

    from benchmarks import table5_supersteps

    print("name,us_per_call,derived")
    for r in table5_supersteps.run(args.scale):
        print(r, flush=True)


if __name__ == "__main__":
    main()
