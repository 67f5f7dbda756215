"""The readings a cell's correctness limit is set from, on the chip at
the cell's own size, each run a short window at the cell's own load.

    python3 bench/control.py --workload qwen05b.docqa --seconds 20 \\
        --seeds 1,2,3
    python3 bench/control.py --workload qwen05b.docqa --seconds 20 \\
        --seeds 1,2,3 --fault unchanged_state,half_batch,altered_token

Without ``--fault``, each seed is one sound run, and over the same
sample of served requests the float8 control takes the program's place:
each position's token is the one the control puts first.  With
``--fault``, each named fault of ``faults.py`` breaks the timed path
underneath, and each seed is one run of each.  Every reading is held
against the cell's limits through ``check.compare`` and printed with its
``correct``: the program's has to be true, the control's and each
fault's false.  All runs share this one process.  The benchmark's own
runs never run the control or a fault.
"""
import argparse
import json
import sys
import time

import faults
import harness


def _verdict(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", default="",
                    help=f"comma-separated, of {sorted(faults.FAULTS)}")
    args = ap.parse_args(argv)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.resolve(spec, args.workload)
    names = [f for f in args.fault.split(",") if f]
    for f in names:
        if f not in faults.FAULTS:
            ap.error(f"unknown fault {f!r} (have {sorted(faults.FAULTS)})")
    harness.setup()
    rows = []  # (seed, what ran, max_logit_gap, correct)
    for seed in [int(s) for s in args.seeds.split(",")]:
        for name in names or [None]:
            out = harness.run(cell, seed, args.seconds, False,
                              t_start=time.perf_counter(), control=not names,
                              fault=faults.FAULTS.get(name))
            runs = [(name or "program", out["checks"])]
            if not names:
                runs.append(("float8_control", out["control"]))
            for what, checks in runs:
                rows.append((seed, what, checks["max_logit_gap"]["value"],
                             _verdict(checks)))
                print(f"# seed {seed}, {what}: " + ", ".join(
                    f"{k} {c['value']} (limit {c['limit']})"
                    for k, c in checks.items()) + f"; correct {rows[-1][3]}",
                    file=sys.stderr, flush=True)
    print("seed,run,max_logit_gap,correct")
    for r in rows:
        print(",".join(str(x) for x in r))
    summary = {"workload": args.workload}
    for what in dict.fromkeys(r[1] for r in rows):
        gaps = [r[2] for r in rows if r[1] == what]
        summary[what] = {"min": min(gaps), "max": max(gaps),
                         "correct": [r[3] for r in rows if r[1] == what]}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
