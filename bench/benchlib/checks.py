"""The comparison that decides ``correct``.

Every request the window completed is checked against the plain
reference (:mod:`reference`), and so is what the device computed for it:

=====================  ==================================================
``unserved``           window requests that failed or never came back
``layout_faults``      served layouts that are not a bijection onto the
                       allocation with each node holding its capacity
``j_gap``              largest gap between a served J value and the
                       recount of the served layout
``off_device``         requests whose refine stage did not run on
                       ``device[<platform>]``
``engine_missing``     solved requests with no device engine snapshot,
                       or no ladder end keys from the device refiner, to
                       check
``row_faults``         device rows (current or best-seen) that break the
                       allocation's capacities
``count_gap``          largest gap between the device's integer count
                       state and the recount of the rows it holds
``key_gap``            largest gap between a (J_max, J_sum) the device
                       computed (each ladder's end key, each row's
                       best-seen key) and the recount
``unmoved_rows``       ladders whose end state is still their start
``served_above_best``  requests whose served (J_max, J_sum) is worse than
                       the best row the device handed back
``cache_faults``       requests served from the plan cache whose layout is
                       not the one served for that problem earlier in the
                       run
=====================  ==================================================

A request served from the plan cache ran nothing on the device; it is
held to the layout its problem's solve served, which the device checks
covered.

Each is exact (integers), so each limit is 0.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from . import reference

__all__ = ["LIMITS", "check_run", "device_readings", "verdict"]

LIMITS: Dict[str, float] = {
    "unserved": 0, "layout_faults": 0, "j_gap": 0, "off_device": 0,
    "engine_missing": 0, "row_faults": 0, "count_gap": 0, "key_gap": 0,
    "unmoved_rows": 0, "served_above_best": 0, "cache_faults": 0,
}


def device_readings(table, record, capacities: Sequence[int],
                    ladder_keys: Sequence) -> Dict[str, float]:
    """Recount one engine's snapshot: its rows, count state and keys."""
    snap = record.snapshot
    caps = np.asarray(capacities, dtype=np.int64)
    N = caps.size
    nodes, best = snap["nodes"], snap["best_nodes"]
    row_faults = sum(int(not reference.capacities_hold(r, caps))
                     for r in np.concatenate([nodes, best]))
    counts = reference.count_state(table, nodes, N)
    count_gap = int(np.abs(counts - np.asarray(snap["counts"])).max())
    jmax, jsum = reference.keys(counts)
    bjmax, bjsum = reference.keys(reference.count_state(table, best, N))
    gaps = [np.abs(np.asarray(snap["best_jmax"]) - bjmax).max(),
            np.abs(np.asarray(snap["best_jsum"]) - bjsum).max()]
    K = record.k
    lk = np.asarray(ladder_keys, dtype=np.float64).reshape(-1, 2)
    gaps += [np.abs(lk[:, 0] - jmax[:K]).max(),
             np.abs(lk[:, 1] - jsum[:K]).max()]
    unmoved = int((nodes[:K] == record.start[None, :]).all(axis=1).sum())
    cand = list(zip(jmax[:K], jsum[:K])) + list(zip(bjmax[:K], bjsum[:K]))
    return {"row_faults": row_faults, "count_gap": count_gap,
            "key_gap": float(max(gaps)), "unmoved_rows": unmoved,
            "best_key": min(cand)}


def check_run(config: dict, records: List[dict], platform: str,
              table=None) -> Dict[str, float]:
    """Readings of every check over the run's window requests.

    ``records`` are the loop's per-request records (see
    :func:`cell.closed_loop`); each carries the request's problem and
    capacities, the served solution (or the error) and the device engine
    records it produced."""
    if table is None:
        table = neighbour_table(config)
    out = {name: 0 for name in LIMITS}
    layouts: Dict[int, np.ndarray] = {}     # problem -> its served layout
    for rec in records:
        sol = rec.get("solution")
        if sol is None:
            out["unserved"] += 1
            continue
        caps = rec["capacities"]
        a = np.asarray(sol["assignment"])
        if not reference.capacities_hold(a, caps):
            out["layout_faults"] += 1
            continue
        jmax, jsum = reference.keys(reference.count_state(table, a,
                                                          len(caps)))
        out["j_gap"] = max(out["j_gap"], abs(sol["j_max"] - float(jmax[0])),
                           abs(sol["j_sum"] - float(jsum[0])))
        stage = sol["engine_stage"]
        if stage is None or stage.get("backend") != f"device[{platform}]":
            out["off_device"] += 1
        if sol["from_cache"]:
            first = layouts.get(rec["problem"])
            if first is None or not np.array_equal(first, a):
                out["cache_faults"] += 1
            continue
        layouts.setdefault(rec["problem"], a)
        engines = [e for e in rec["engines"]
                   if e.snapshot is not None and e.ladder_keys is not None]
        if len(engines) != 1:
            out["engine_missing"] += 1
            continue
        dev = device_readings(table, engines[0], caps,
                              engines[0].ladder_keys)
        for name in ("row_faults", "unmoved_rows"):
            out[name] += dev[name]
        for name in ("count_gap", "key_gap"):
            out[name] = max(out[name], dev[name])
        if (sol["j_max"], sol["j_sum"]) > tuple(map(float, dev["best_key"])):
            out["served_above_best"] += 1
    return out


def neighbour_table(config: dict):
    return reference.neighbours(config["dims"], config["stencil"]["offsets"],
                                config["periodic"])


def verdict(readings: Dict[str, float]) -> bool:
    return all(readings[name] <= limit for name, limit in LIMITS.items())
