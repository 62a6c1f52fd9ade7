#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, when the cell is defined.

    python3 benchmark/sweep.py --workload <cell> --rates 3,4,5,6,7 \
        --step-seconds 40 --seed 7

One server lifetime (the cell brought up exactly as run.py does), then
one window of the cell's own mix per rate, rising, each drained before
the next. Prints a table, one JSON line per step. The knee is the
highest rate whose backlog does not grow over the step (requests in
flight at the end of the step's last quarter no higher than at the end
of its second quarter, within the mix's own variation) and whose
failures are 0; the cell then runs at 0.8 x that rate, written into
``benchmark/cells/<cell>.json``. The benchmark itself never searches
for a rate: this is run by hand, and its table goes into PERF.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as B  # noqa: E402
from benchmark.lib import loadgen, prom  # noqa: E402
from benchmark.lib import reduce as R  # noqa: E402
from benchmark.lib.children import CHILDREN, HarnessFailure  # noqa: E402


def in_flight(log: list, t: float) -> int:
    """Requests due by t that had not ended by t."""
    return sum(1 for r in log if r["due"] is not None and r["due"] <= t
               and (r.get("end") is None or r["end"] > t))


def step_row(rate: float, got: dict, seconds: float) -> dict:
    log = got["log"]
    win = R.in_window(log, seconds)
    bad = [r for r in win if loadgen.malformed(r)]
    row = {"rate_rps": rate, "attempted": len(win), "failed": len(bad)}
    for name, series, q in (("ttft_p50_ms", "ttft_ms", 50),
                            ("ttft_p95_ms", "ttft_ms", 95),
                            ("itl_p95_ms", "itl_ms", 95),
                            ("tpot_p50_ms", "tpot_ms", 50)):
        vals = R.series(log, seconds, series)
        row[name] = round(R.percentile(vals, q), 1) if vals else None
    row["output_tok_s"] = round(
        R.series(log, seconds, "window_tokens")[0] / seconds, 1)
    row["in_flight_at_quarters"] = [
        in_flight(log, seconds * f) for f in (0.25, 0.5, 0.75, 1.0)]
    depth = [prom.total(p, "engine_queue_depth_count")
             for p in got["polls"]]
    n = max(1, len(depth) // 4)
    row["queue_depth_mean_by_quarter"] = [
        round(sum(depth[i * n:(i + 1) * n]) / n, 2) for i in range(4)]
    row["drain_s"] = round(max(
        [r["end"] for r in log if r.get("end") is not None] + [seconds])
        - seconds, 1)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, rising")
    ap.add_argument("--step-seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    rates = [float(x) for x in args.rates.split(",")]
    c = B.Cell(ROOT, args.workload, args.seed, False,
               f"sweep-{args.workload}")
    if c.mix["loop"] != "open":
        print("benchmark/sweep.py: a closed loop has no rate to sweep",
              file=sys.stderr)
        return 2
    rows = []
    try:
        c.bring_up()
        for rate in rates:
            mix = dict(c.mix, rate_rps=rate)
            lo = c.log_size()
            got, _ = c.window(mix, args.seed, args.step_seconds)
            row = step_row(rate, got, args.step_seconds)
            compiled = c.compiles_between(lo, c.log_size())
            row["compiles_in_step"] = len(compiled)
            row["compiled"] = compiled[:8]
            rows.append(row)
            print(json.dumps(row), flush=True)
            time.sleep(2.0)
        c.shut_down()
    except HarnessFailure as e:
        print(f"benchmark/sweep.py: {e}", file=sys.stderr)
        return 3
    finally:
        CHILDREN.stop_all()
    with open(os.path.join(c.run_dir, "sweep.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
