"""The knee of an open-loop cell: the highest fixed rate at which the
program keeps up, found once by a sweep on the chip.

    python3 bench/knee_sweep.py --workload qwen05b.docqa --seed N \\
        --seconds 30 --rates 0.8,1.0,1.2,1.4,1.6,2.0

Each rate is one run of the cell with the traffic file's rate replaced,
in this one process.  Per rate it prints the requests due in the window,
those answered within it, the backlog (due but not yet admitted) at the
window's close, and the median queue wait of the window's first and
second halves: a backlog that grows shows as a second half that waits
far longer than the first.  The chosen rate, four fifths of the knee,
is written into the traffic file by hand.
"""
import argparse
import json
import sys
import time

import harness
from stats import percentile


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.resolve(spec, args.workload)
    harness.setup()
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.mix = dict(cell.mix, arrivals=dict(cell.mix["arrivals"],
                                                 rate_per_s=rate))
        keep: dict = {}
        out = harness.run(cell, args.seed, args.seconds, False,
                          t_start=time.perf_counter(), keep=keep)
        d = keep["data"]
        due = [r for r in d.reqs.values() if d.in_window(r.due)]
        done = sum(1 for r in due if r.state == "DRAINED"
                   and r.ended is not None and r.ended <= d.t1)
        backlog = sum(1 for r in due
                      if r.admitted is None or r.admitted > d.t1)
        mid = (d.t0 + d.t1) / 2
        halves = [[r.admitted - r.due for r in due
                   if r.admitted is not None and (r.due < mid) == first]
                  for first in (True, False)]
        waits = [percentile(h, 50) for h in halves]
        p90 = harness.end_to_end(d, False, 0.0)["ttft_p90_ms"][0]
        rows.append((rate, len(due), done, backlog, *waits, p90,
                     out["correct"]))
        print(f"# rate {rate}: due {len(due)}, answered in the window "
              f"{done}, backlog at the close {backlog}, queue wait p50 "
              f"first half {waits[0]}, second half {waits[1]}",
              file=sys.stderr, flush=True)
    print("rate_per_s,due,answered_in_window,backlog_at_close,"
          "wait_p50_first_half_s,wait_p50_second_half_s,ttft_p90_ms,correct")
    for r in rows:
        print(",".join(str(x) for x in r))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
