#!/usr/bin/env python3
"""The on-chip benchmark of the mapping service: one cell, one run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a host with the chips the cell asks
for.  The cell, its configuration, its traffic and its per-layer metrics
are found by name from ``BENCHMARK.json`` (see ``bench/benchlib/cell.py``).

A run builds the cell's requests from ``--seed``, starts a plan server
with a fresh in-memory plan cache, warms the cell's shapes with one solve,
then measures for ``--seconds``: one closed-loop client sends a request,
waits for its mapping, and sends the next.  After the window it checks
every served mapping and what the device computed for it against the
plain reference (``bench/benchlib/checks.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics from a profiler trace of the window),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with its limit.  The same numbers end stderr.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result; without the program beside it, 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")
    if not (ROOT / "src" / "repro").is_dir():
        log(f"{ROOT / 'src' / 'repro'} not found: the benchmark runs from "
            "a checkout of the repository")
        return 2
    if not (ROOT / "BENCHMARK.json").is_file():
        log(f"{ROOT / 'BENCHMARK.json'} not found")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from benchlib.cell import resolve, run_cell
    from benchlib.jaxenv import NoDevice, enable_compile_cache, find_devices

    cell = resolve(ROOT, args.workload)
    cache = enable_compile_cache(ROOT)
    try:
        device = find_devices(cell.chips)
    except NoDevice as e:
        log(f"{e}; this benchmark runs only on a TPU")
        return 1
    log(f"{args.workload}: {device['kind']} x{device['count']}, seed "
        f"{args.seed}, {args.seconds} s, trace {args.trace}, compile cache "
        f"{cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device, T_START, log)
    for name, check in result["checks"].items():
        log(f"check {name} = {check['value']} (limit {check['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
