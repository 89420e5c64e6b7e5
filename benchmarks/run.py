"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows plus the paper-claim
validation verdicts (EXPERIMENTS.md cites this output).

  PYTHONPATH=src python -m benchmarks.run [--fast] [--skip fig8,...]
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="subsample the Fig.8 instance suite")
    ap.add_argument("--skip", default="",
                    help="comma list: fig8,fig67,fig9,roofline,kernels")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    skip = set(args.skip.split(",")) if args.skip else set()

    from . import (exchange_time, instantiation_time, kernels_bench,
                   reduction_suite, roofline_table)

    claims = []
    suites = []
    if "fig8" not in skip:
        suites.append(("fig8 (reduction suite, 144 instances)",
                       lambda: reduction_suite.run(fast=args.fast),
                       reduction_suite.validate_claims))
    if "fig67" not in skip:
        suites.append(("fig6/7 (exchange-time model)", exchange_time.run,
                       exchange_time.validate_claims))
    if "fig9" not in skip:
        suites.append(("fig9 (instantiation time)", instantiation_time.run,
                       instantiation_time.validate_claims))
    if "roofline" not in skip:
        suites.append(("roofline (from dry-run artifacts)",
                       roofline_table.run, None))
    if "kernels" not in skip:
        suites.append(("kernels (reference micro)", kernels_bench.run, None))

    print("name,us_per_call,derived")
    for title, fn, validate in suites:
        t0 = time.time()
        rows = fn()
        for r in rows:
            extra = ""
            for k in ("dominant", "ci95", "n", "useful_ratio"):
                if k in r:
                    extra += f",{k}={r[k]}"
            print(f"{r['name']},{r['us_per_call']:.2f},{r['derived']:.4f}"
                  + extra)
        sys.stderr.write(f"# {title}: {len(rows)} rows in "
                         f"{time.time() - t0:.1f}s\n")
        if validate:
            claims.extend(validate(rows))
    if claims:
        print("\n# paper-claim validation")
        for c in claims:
            print("# " + c)
        n_fail = sum(c.startswith("FAIL") for c in claims)
        sys.stderr.write(f"# claims: {len(claims) - n_fail}/{len(claims)} "
                         "pass\n")


if __name__ == "__main__":
    main()
