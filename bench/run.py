"""Run one cell of the benchmark once and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json``. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` the device's busy time and a
``breakdown``), then ``check``: each number the correctness check
compared, beside its limit. The same numbers end standard error. Without
a TPU, or with fewer chips than the cell asks for, it exits 1 and prints
no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # a dry run of the whole path on the CPU at a tiny size (tests only)
    ap.add_argument("--tiny-cpu", action="store_true", help=argparse.SUPPRESS)
    # keep a slice of the device trace in this directory (recording one)
    ap.add_argument("--keep-trace", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    import harness
    try:
        cell = harness.find_cell(harness.load_benchmark(), args.workload)
        line = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, tiny=args.tiny_cpu,
            keep_trace=Path(args.keep_trace) if args.keep_trace else None)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 1
    for name, n in line["check"].items():
        print(f"check {name}: {n['value']!r} (limit {n['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
