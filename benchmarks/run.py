"""Count reports: one section per paper table + roofline rows.

    PYTHONPATH=src python -m benchmarks.run [--scale 10]

Prints ``name,us_per_call,derived`` CSV (the counts are in ``derived``):
  table5/*   — superstep counts under the three compilers (paper Tab.5)
  roofline/* — per-cell dry-run roofline terms (from experiments/dryrun)

Times are measured on the chip, by ``benchmarks/palgol_chip/run.py``.
"""

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=10,
                    help="log2 graph size for table5 (default 2^10)")
    ap.add_argument("--sections", default="table5,roofline")
    args = ap.parse_args()
    sections = set(args.sections.split(","))

    from repro import compile_cache

    compile_cache.enable()

    print("name,us_per_call,derived")
    rows = []
    if "table5" in sections:
        from benchmarks import table5_supersteps

        rows += table5_supersteps.run(args.scale)
        _flush(rows)
    if "roofline" in sections:
        from benchmarks import roofline_report

        rows += roofline_report.run()
        _flush(rows)


_printed = 0


def _flush(rows):
    global _printed
    for r in rows[_printed:]:
        print(r, flush=True)
    _printed = len(rows)


if __name__ == "__main__":
    main()
