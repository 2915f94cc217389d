"""Windows of one cell in one process, for the measurements that set the
benchmark: a sweep of offered rates (the knee), and the check's readings
over many seeds with the control beside them. Not run by the benchmark.

  python3 bench/calibrate.py --workload <cell> --seconds S --seeds 1 2 3 \\
      [--rates 1.5 2.0 2.5] [--control] [--out chiprun_out/x.json]

Each (rate, seed) window makes its own weights, serves its own schedule,
frees the program and runs the reference, as a run does; only the
process, and so the compiled programs, is shared. One JSON line per
window on stdout, and all of them in ``--out``.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402


def summary(cell, run, res, rate) -> dict:
    import harness
    row = {"rate_per_s": rate, "requests": len(run.requests),
           "setup_s": run.setup_s, "late_p50_ms": run.late_p50_ms,
           "late_max_ms": run.late_max_ms,
           "compiles_in_window": run.compiles_in_window,
           "memory_peak_bytes": run.memory_peak_bytes}
    for mt in cell.end_to_end:
        row[mt["name"]] = harness.load_reader(mt["name"])(run)
    steps = run.engine1["steps"] - run.engine0["steps"]
    slots = run.engine1["step_slots"] - run.engine0["step_slots"]
    row["steps_per_s"] = steps / run.seconds
    row["occupancy"] = slots / max(1, steps)
    row["backlog"] = run.backlog
    row["arena_blocks"] = run.n_blocks
    row["arena_peak_in_use"] = run.pool1.get("peak_blocks_in_use")
    row["check"] = res["numbers"]
    gaps = np.sort(res["gaps"])[::-1]
    row["top_gaps"] = gaps[:5].tolist()
    if "control_widest_gap" in res:
        row["control_widest_gap"] = res["control_widest_gap"]
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=[None])
    ap.add_argument("--zip", action="store_true",
                    help="pair the i-th rate with the i-th seed")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--tiny-cpu", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import harness
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    m, dev, peaks = harness.prepare(cell, tiny=args.tiny_cpu)
    compiles = harness._CompileCounter()
    rows = []
    t_start = T_START
    pairs = (list(zip(args.rates, args.seeds)) if args.zip else
             [(r, s) for r in args.rates for s in args.seeds])
    for rate, seed in pairs:
        run, res = harness.one_window(
            cell, m, seed, args.seconds, trace=False, t_start=t_start,
            compiles=compiles, rate_per_s=rate, control=args.control)
        row = dict(seed=seed, device=dev, **summary(cell, run, res, rate))
        rows.append(row)
        print(json.dumps(row), flush=True)
        t_start = time.monotonic()
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
