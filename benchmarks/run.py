"""Benchmark orchestrator — one bench per paper table/figure + the TPU
adaptations.  Prints ``name,us_per_call,derived`` CSV lines and writes
the same rows as machine-readable JSON (name -> {us, derived}) so the
perf trajectory can be tracked PR-over-PR.

  PYTHONPATH=src python -m benchmarks.run [--skip-subprocess] [--json PATH]
                                          [--only SUBSTR]

``--only SUBSTR`` re-measures just the rows whose name contains SUBSTR
(and skips the bench modules that cannot produce a matching row
entirely), so one regressed kernel can be re-timed without re-running
the full suite — the bench-gate's retry path uses ``--only kernel_`` to
re-measure exactly the gated rows.

Benches:
  fig3a_*      XBAR area/timing model          (paper fig. 3a)
  fig3b_*      1-to-N DMA microbenchmark       (paper fig. 3b)
  fig3c_*      Occamy matmul roofline + kernel (paper fig. 3c)
  fig3b_tpu_*  collective-bytes hierarchy on the TPU mesh (adaptation)
  kernel_*     Pallas kernel interpret-mode sanity timings
  kernel_serve_* / kernel_paged_*  paged-KV serving rows: decode
               tokens/s, prefix-cache prefill latency, chunked-prefill
               supertile kernel vs reference gather (bench_serve.py)
  kernel_serve_load_*  async serve-loop load rows: sustained tok/s +
               TTFT/ITL percentiles under a seeded Poisson trace
               (bench_serve_load.py)

The ``kernel_serve_trace_overhead`` row gates the ``repro.obs`` tracing
layer at <5% decode overhead when armed.  To inspect a trace offline,
``python -m repro.obs.analyze TRACE.json`` prints the multicast-
efficiency report (B-fetches avoided, prefix pages multicast, fabric
bytes per mode, TTFT decomposition) as a table.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys

from repro import runtime

DEFAULT_JSON = "BENCH_kernels.json"

# (module, row-name prefixes it emits, accepts only=, needs subprocess)
SOURCES = (
    ("benchmarks.bench_area", ("fig3a_",), False, False),
    ("benchmarks.bench_microbench", ("fig3b_",), False, False),
    ("benchmarks.bench_matmul_roofline", ("fig3c_",), False, False),
    ("benchmarks.bench_collective_bytes", ("fig3b_tpu_",), False, True),
    ("benchmarks.bench_kernels", ("kernel_",), True, False),
    ("benchmarks.bench_serve", ("kernel_serve_", "kernel_paged_"), True, False),
    ("benchmarks.bench_serve_load",
     ("kernel_serve_load_", "kernel_serve_spec_"), True, False),
)


def rows_to_json(rows: list[str]) -> dict[str, dict]:
    out: dict[str, dict] = {}
    for r in rows:
        name, us, derived = r.split(",", 2)
        out[name] = {"us": float(us), "derived": derived}
    return out


_ALL_PREFIXES = tuple(p for _, ps, _, _ in SOURCES for p in ps)


def _may_match(only: str, prefixes: tuple[str, ...]) -> bool:
    """Can a module emitting ``prefixes``-named rows produce a row whose
    name contains ``only``?  True when the filter overlaps one of the
    module's prefixes in either direction (``kernel_`` selects the
    ``kernel_serve_*`` module; ``kernel_ssd`` selects the
    ``kernel_``-emitting module).  A filter anchored at some *other*
    module's prefix (``fig3a_area``) can be skipped here; an unanchored
    substring (``ssd``, ``sweep``) could sit anywhere in a row's tail,
    so every module must run and the rows are filtered afterwards."""
    if any(only in p or p in only for p in prefixes):
        return True
    return not any(only.startswith(p) for p in _ALL_PREFIXES)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--json", default=None, metavar="PATH",
                    help=f"output JSON path (default: {DEFAULT_JSON}; a "
                         f"--only run writes no JSON unless a path is given "
                         f"— a partial row set must never clobber the "
                         f"committed baseline)")
    ap.add_argument("--skip-subprocess", action="store_true",
                    help="skip benches that spawn subprocesses (CI)")
    ap.add_argument("--only", default=None, metavar="SUBSTR",
                    help="only measure rows whose name contains SUBSTR")
    args = ap.parse_args()
    runtime.setup_compile_cache()

    rows: list[str] = []
    skipped_subprocess: list[str] = []
    for mod_name, prefixes, takes_only, subprocess_ in SOURCES:
        if args.only is not None and not _may_match(args.only, prefixes):
            continue
        if subprocess_ and args.skip_subprocess:
            skipped_subprocess.append(mod_name)
            continue
        mod = importlib.import_module(mod_name)
        got = mod.run(only=args.only) if takes_only else mod.run()
        if args.only is not None:
            got = [r for r in got if args.only in r.split(",", 1)[0]]
        rows += got

    if args.only is not None and not rows:
        hint = (
            f" (note: --skip-subprocess excluded {', '.join(skipped_subprocess)},"
            f" which could have matched)" if skipped_subprocess else ""
        )
        raise SystemExit(f"error: --only {args.only!r} matched no bench rows{hint}")

    print("name,us_per_call,derived")
    for r in rows:
        print(r)

    json_path = args.json
    if json_path is None:
        if args.only is not None:
            # a filtered run holds a partial row set: writing it to the
            # default path would silently replace the committed baseline
            # and un-gate every filtered-out kernel
            print("# --only run: no JSON written (pass --json PATH to keep "
                  "the partial rows)", file=sys.stderr)
            return
        json_path = DEFAULT_JSON
    with open(json_path, "w") as f:
        json.dump(rows_to_json(rows), f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {json_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
