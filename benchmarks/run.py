"""Benchmark harness — one module per paper table/figure + substrate benches.

Prints ``name,us_per_call,derived`` CSV rows. Select subsets with
``python -m benchmarks.run [fig3] [fig4] [fig5] [kernels] [distributed]``.

``--json PATH`` additionally writes the selected suites' rows as structured
JSON (suite -> [{name, us_per_call, derived}]) so the perf trajectory is
machine-readable, e.g.::

    python -m benchmarks.run kernels --json BENCH_kernels.json

``--smoke`` shrinks shapes/iterations on the suites that support it — the CI
harness-smoke job runs this so the perf harness itself cannot rot between
perf PRs (numbers are meaningless; only that every row still produces).
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time


def _parse_row(row: str):
    name, us, derived = row.split(",")
    return {
        "name": name,
        "us_per_call": float(us),
        "derived": float(derived) if derived else None,
    }


def main() -> None:
    from benchmarks import (bench_classification, bench_distributed,
                            bench_dp, bench_kernels, bench_regression,
                            bench_serve, bench_serve_load, bench_surrogate,
                            bench_telemetry, bench_tiered)

    suites = {
        "fig3": bench_surrogate.run,
        "fig4": bench_regression.run,
        "fig5": bench_classification.run,
        "surrogate": bench_surrogate.run_surrogate,
        "kernels": bench_kernels.run,
        "distributed": bench_distributed.run,
        "serve": bench_serve.run,
        "serve_load": bench_serve_load.run,
        "tiered": bench_tiered.run,
        "telemetry": bench_telemetry.run,
        "dp": bench_dp.run,
    }
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("suite", nargs="*",
                        help=f"suites to run (default: all of {list(suites)})")
    parser.add_argument("--json", metavar="PATH", default=None,
                        help="write suite rows as structured JSON to PATH")
    parser.add_argument("--smoke", action="store_true",
                        help="tiny shapes/iters (CI harness smoke; numbers "
                             "are not meaningful)")
    args = parser.parse_args()

    unknown = [s for s in args.suite if s not in suites]
    if unknown:
        parser.error(f"unknown suite(s) {unknown}; choose from {list(suites)}")
    if args.json:
        # Fail fast on an unwritable path, before minutes of benching —
        # side-effect-free (no stray empty artifact if a suite later dies).
        parent = os.path.dirname(args.json) or "."
        if not os.path.isdir(parent) or not os.access(parent, os.W_OK):
            parser.error(f"--json parent directory not writable: {parent!r}")
        if os.path.isdir(args.json):
            parser.error(f"--json path is a directory: {args.json!r}")
    selected = args.suite or list(suites)
    from repro.launch import compile_cache

    compile_cache.enable()
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    results = {}
    for name in selected:
        fn = suites[name]
        kwargs = (
            {"smoke": True}
            if args.smoke and "smoke" in inspect.signature(fn).parameters
            else {}
        )
        results[name] = fn(**kwargs) or []
    total = time.perf_counter() - t0
    print(f"# total_seconds,{total:.1f},", file=sys.stderr)

    if args.json:
        import jax

        payload = {
            "meta": {
                "backend": jax.default_backend(),
                "total_seconds": round(total, 1),
                "suites": selected,
            },
            "suites": {
                name: [_parse_row(r) for r in rows]
                for name, rows in results.items()
            },
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
