#!/usr/bin/env python3
"""The readings that the correctness limits are set from, and the control.

    python3 bench/control.py --workload <name> --seeds <n> --seconds <s>

For each of ``n`` seeds (``--first-seed`` onward) it runs the cell's
traffic for a short window at the cell's own size, as a benchmark run
does, and prints the check's readings for the program.  Then, for every
device engine snapshot of that window, it puts the reference in the
program's place: the keys the device reports (each ladder's end key, each
row's best-seen key) are computed from the snapshot's count state in
bfloat16, the precision below the float32 the configuration states, and
the same check reads them: the program's readings with the control's
``key_gap`` in place go through the verdict that decides ``correct``,
which has to come out false.

One process runs every seed, on a TPU; the benchmark's own runs never run
this.  The last line of stdout is a JSON object with one entry per seed.
It exits 1 when the control passes, or the program fails, on any seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def bf16_keys(counts):
    """``(J_max, J_sum)`` per row of an ``(R, N, k)`` count state, computed
    as the device kernel computes them but in bfloat16."""
    import jax.numpy as jnp
    import numpy as np
    c = jnp.asarray(counts).astype(jnp.bfloat16)
    w = jnp.ones((c.shape[2],), jnp.bfloat16)
    jmax = jnp.einsum("rnk,k->rn", c, w).max(axis=1)
    jsum = jnp.einsum("rk,k->r", c.sum(axis=1), w)
    return (np.asarray(jmax, dtype=np.float64),
            np.asarray(jsum, dtype=np.float64))


def control_key_gap(records, table):
    """The check's ``key_gap`` with bfloat16 keys in the device's place."""
    import numpy as np
    from benchlib import checks, reference
    gap = 0.0
    for rec in records:
        for eng in rec.get("engines", []):
            if eng.snapshot is None:
                continue
            snap = dict(eng.snapshot)
            N = len(rec["capacities"])
            best_counts = reference.count_state(table, snap["best_nodes"], N)
            snap["best_jmax"], snap["best_jsum"] = bf16_keys(best_counts)
            jmax, jsum = bf16_keys(snap["counts"])
            ladder = np.stack([jmax[:eng.k], jsum[:eng.k]], axis=1)
            ctl = type(eng)(eng.start, eng.rows, eng.k)
            ctl.snapshot = snap
            dev = checks.device_readings(table, ctl, rec["capacities"],
                                         ladder)
            gap = max(gap, dev["key_gap"])
    return gap


def control_readings(program, records, table):
    """The readings of a run with the control in the program's place: the
    program's own, with the control's ``key_gap``."""
    return dict(program, key_gap=control_key_gap(records, table))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from benchlib import checks
    from benchlib.cell import resolve, run_window
    from benchlib.jaxenv import NoDevice, enable_compile_cache, find_devices
    cell = resolve(ROOT, args.workload)
    enable_compile_cache(ROOT)
    try:
        device = find_devices(cell.chips)
    except NoDevice as e:
        print(f"[control] {e}", file=sys.stderr)
        return 1
    out = []
    for i in range(args.seeds):
        seed = args.first_seed + i
        m = run_window(cell, seed, args.seconds, None, device,
                       time.perf_counter(),
                       lambda msg: print(f"[control] {msg}", file=sys.stderr))
        program = checks.check_run(cell.config, m.records,
                                   device["platform"], m.table)
        control = control_readings(program, m.records, m.table)
        row = {"seed": seed, "served": len(m.records),
               "program": program, "program_correct": checks.verdict(program),
               "control": control, "control_correct": checks.verdict(control)}
        print(f"[control] seed {seed}: program correct "
              f"{row['program_correct']}, control correct "
              f"{row['control_correct']}, control key_gap "
              f"{control['key_gap']} (limit {checks.LIMITS['key_gap']})",
              file=sys.stderr, flush=True)
        out.append(row)
    print(json.dumps({"workload": args.workload, "device": device,
                      "seeds": out}), flush=True)
    separated = all(r["program_correct"] and not r["control_correct"]
                    for r in out)
    return 0 if separated else 1


if __name__ == "__main__":
    sys.exit(main())
