#!/usr/bin/env python3
"""Find a serving cell's knee: one server, one set-up, a window at each of
a list of offered rates.

    python3 benchmark/sweep.py --workload arima111.serve-small --seed 1 \
        --seconds 12 --rates 4,8,12,16,24

Done ONCE when a serving mix is defined (``README.md`` keeps what was seen);
the cell then offers the rate written in its traffic file and the benchmark
never searches for one.  Each rate is one line: offered and completed
requests and rows per second, the tails, what was refused, and how long the
queue took to drain past the window — a queue that outlasts its window more
at every step is the knee behind it.  Rates run in the order given, each
with its own seed, so no request id repeats.
"""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as run_mod  # noqa: E402


def main(argv=None) -> int:
    def more(ap):
        ap.add_argument("--rates", required=True,
                        help="comma-separated requests/s")
        ap.add_argument("--arrivals", default="",
                        help="comma-separated arrival kinds to try at every "
                             "rate (default: the mix's own)")

    args = run_mod.parse_args(argv, more)
    run, kind = run_mod.prepare(args)
    run.state = kind.setup(run)
    try:
        kinds = [k for k in args.arrivals.split(",") if k] or [None]
        windows = [(float(r), k) for k in kinds
                   for r in args.rates.split(",")]
        for k, (rate, arrival) in enumerate(windows):
            run.seed = int(args.seed) + k
            run.cell.traffic["rate"] = rate
            if arrival:
                run.cell.traffic["arrival"] = {"kind": arrival}
            run.compiles.open_window()
            res = kind.measure(run, run.state)
            run.compiles.close_window()
            done = res["done_s"]
            rows = res["schedule"]["rows"]
            print(json.dumps({
                "rate": rate, "arrival": run.cell.traffic.get("arrival"),
                "attempted": res["attempted"],
                "failed": res["failed"], "refused": res["refused"],
                "offered_rows_per_s": float(rows.sum() / run.seconds),
                "completed_per_s": float(
                    (done <= run.seconds).sum() / run.seconds),
                "drain_past_window_s": res["window_wall_s"] - run.seconds,
                **res["values"],
                "batches": res["counters"]["batches_run"],
                "knobs": res["knobs"],
                "compiles_in_window": run.compiles.in_window(),
                "device": run.device}), flush=True)
    finally:
        kind.teardown(run, run.state)
        shutil.rmtree(run.work_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
