"""Base-vs-refined mapper comparison: J_sum, J_max, and wall-time.

For every (grid shape, node layout, stencil) instance, run each applicable
base mapper and its refinement variants (``refined:<base>`` swap local
search, ``refined2:<base>`` alternating j_sum/j_max schedule,
``annealed:<base>`` schedule + simulated-annealing ladder,
``portfolio:<base>`` K batched annealing starts, ``sharded:<base>`` the
portfolio partitioned across worker processes with optional adaptive
restart control) and report the cost drops and the refinement overhead.  Node layouts include ragged tails (elastic
pods after failures) — the heterogeneous case Nodecart cannot handle but
the refiners improve for free.  The ``plan`` stencil rows are
byte-weighted (``launch.mesh.stencil_for_plan``, weights in GiB): for
those, costs and refinement are scored in bytes through the refiners'
``weighted="auto"`` path, alongside the unit-weight rows.

Variant spellings accept bracket options (``portfolio[k=8]``), so the
sweep drives the same name grammar as ``get_mapper``.

  PYTHONPATH=src python -m benchmarks.refine_suite            # full sweep
  PYTHONPATH=src python -m benchmarks.refine_suite --tiny     # smoke (<5 s)
  PYTHONPATH=src python -m benchmarks.refine_suite \
      --variants refined,annealed,portfolio[k=8] --instances ragged
  PYTHONPATH=src python -m benchmarks.refine_suite --tiny --linksim
  PYTHONPATH=src python -m benchmarks.refine_suite --json out.json
  PYTHONPATH=src python -m benchmarks.refine_suite --instances ragged \
      --variants "annealed,portfolio[k=8],sharded[shards=4,k=64,restarts=auto]"
  PYTHONPATH=src python -m benchmarks.refine_suite --device \
      --json results/BENCH_7.json
"""
import argparse
import json
import math
import re
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (CartGrid, MapperInapplicable, Stencil, evaluate,
                        get_mapper)
from repro.core.mapping import MAPPERS

# (label, dims, node_sizes) — ragged tails marked by uneven sizes
INSTANCES = [
    ("2d-48x48-hom", (48, 48), [48] * 48),
    ("2d-50x48-hom", (50, 48), [48] * 50),
    ("2d-16x28-ragged", (16, 28), [256, 192]),
    ("3d-8x8x8-hom", (8, 8, 8), [64] * 8),
    ("3d-12x8x8-ragged", (12, 8, 8), [128] * 5 + [96, 32]),
]
TINY_INSTANCES = [
    ("2d-8x8-hom", (8, 8), [16] * 4),
    ("2d-6x8-ragged", (6, 8), [16, 16, 10, 6]),
    ("3d-4x4x4-hom", (4, 4, 4), [16] * 4),
]


def _plan_stencil(d):
    """Byte-weighted ring stencil of a real (arch, shape) parallelism plan,
    weights rescaled to GiB (an exact power-of-two scale) so tables stay
    readable.  Lazy import: only rows using this stencil pay the jax
    import behind launch.mesh."""
    from repro.configs import get_arch
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import stencil_for_plan
    cfg = get_arch("granite-3-8b")
    shape = ShapeSpec("bench", seq_len=2048, global_batch=16, kind="train")
    st = stencil_for_plan(cfg, shape, multi_pod=(d == 3))
    return Stencil(st.offsets, tuple(w / 2**30 for w in st.weights),
                   name=f"plan-gib-{cfg.name}")


STENCILS = {
    "nn": Stencil.nearest_neighbor,       # 2D 5-point / 3D 7-point
    "comp": Stencil.component,
    "hops": Stencil.nn_with_hops,
    "plan": _plan_stencil,                # byte-weighted (GiB)
}

#: Comparison variants: registry prefix (optionally with bracket options)
#: -> columns.  ScheduledRefiner/PortfolioRefiner own their phase order,
#: so `objective` only applies to the plain `refined` variant.
VARIANTS = ("refined", "refined2", "annealed")


def split_variants(spec):
    """Split a --variants CLI value on commas outside bracket options."""
    from repro.core.mapping import split_mapper_list
    return tuple(split_mapper_list(spec))


def variant_prefix(variant):
    """`portfolio[k=8]` -> `portfolio` (the registry prefix)."""
    return variant.split("[", 1)[0]


def _variant_kwargs(variant, refine_kwargs):
    kwargs = dict(refine_kwargs or {})
    if variant_prefix(variant) != "refined":
        kwargs.pop("objective", None)
    return kwargs


def _linksim_cols(grid, stencil, assign, sizes, suffix, row):
    from repro.analysis.linksim import replay_assignment
    rep = replay_assignment(grid, stencil, assign, sizes,
                            weighted=stencil.is_weighted)
    row[f"dci_max_{suffix}"] = rep.max_dci_pod()
    row[f"dci_total_{suffix}"] = rep.dci_total


def run(tiny: bool = False, mappers=None, variants=VARIANTS,
        refine_kwargs=None, stencils=None, instances=None,
        linksim: bool = False):
    """Returns one row per (instance, stencil, mapper); each row carries
    ``j_sum_<variant>`` / ``j_max_<variant>`` / ``t_<variant>_s`` columns
    (byte-weighted for the ``plan`` stencil rows, with ``weighted=True``
    in the row), plus ``dci_max_*`` replay columns for every row when
    ``linksim`` is set — ragged rows replay on per-pod torus sizes
    (:func:`repro.analysis.linksim.machine_for_nodes`), closing the
    dci==J loop on the elastic path too."""
    instance_rows = TINY_INSTANCES if tiny else INSTANCES
    if instances:
        instance_rows = [r for r in instance_rows if instances in r[0]]
    mappers = mappers or sorted(MAPPERS)
    stencils = stencils or sorted(STENCILS)
    rows = []
    for label, dims, sizes in instance_rows:
        grid = CartGrid(dims)
        for sname in stencils:
            stencil = STENCILS[sname](grid.ndim)
            weighted = stencil.is_weighted
            for mname in mappers:
                try:
                    t0 = time.perf_counter()
                    base_assign = get_mapper(mname).assignment(grid, stencil,
                                                               sizes)
                    t_base = time.perf_counter() - t0
                except MapperInapplicable:
                    continue
                base = evaluate(grid, stencil, base_assign,
                                num_nodes=len(sizes), weighted=weighted)
                ragged = len(set(sizes)) > 1
                row = {
                    "instance": label, "stencil": sname, "mapper": mname,
                    "ragged": ragged, "weighted": weighted,
                    "j_sum_base": base.j_sum, "j_max_base": base.j_max,
                    "t_base_s": t_base,
                }
                if linksim:
                    _linksim_cols(grid, stencil, base_assign, sizes, "base",
                                  row)
                for variant in variants:
                    vm = get_mapper(f"{variant}:{mname}",
                                    **_variant_kwargs(variant, refine_kwargs))
                    t0 = time.perf_counter()
                    v_assign = vm.assignment(grid, stencil, sizes)
                    t_total = time.perf_counter() - t0
                    vc = evaluate(grid, stencil, v_assign,
                                  num_nodes=len(sizes), weighted=weighted)
                    rr = vm.last_result
                    row.update({
                        f"j_sum_{variant}": vc.j_sum,
                        f"j_max_{variant}": vc.j_max,
                        f"swaps_{variant}": rr.swaps,
                        f"t_{variant}_s": rr.wall_time_s,
                        f"t_total_{variant}_s": t_total,
                    })
                    if linksim:
                        _linksim_cols(grid, stencil, v_assign, sizes,
                                      variant, row)
                rows.append(row)
    return rows


def _lex_le(a, b, rtol=0.0):
    """(J_max, J_sum) lexicographic <=, with optional per-component
    relative slack (byte-weighted rows re-evaluate sums in a different
    accumulation order than the refiner's integer-count core, so exact
    float equality is an ulp too strict there).  A genuinely
    lexicographically-<= pair always passes; the slack only rescues pairs
    that lose by ulp-level noise."""
    if a <= b:
        return True
    if math.isclose(a[0], b[0], rel_tol=rtol):
        return a[1] <= b[1] or math.isclose(a[1], b[1], rel_tol=rtol)
    return False


def _key(row, suffix):
    return (row[f"j_max_{suffix}"], row[f"j_sum_{suffix}"])


def _rtol(row):
    return 1e-9 if row.get("weighted") else 0.0


def validate_claims(rows, objective="j_sum", variants=VARIANTS):
    """Machine-checkable verdicts mirroring benchmarks.run conventions.

    ``refined:`` optimizes the configured objective (under j_max it is the
    lexicographic (J_max, J_sum) pair — J_sum alone may grow), so its
    no-worse claim is checked on the metric actually optimized.  The
    scheduled variants select lexicographically by (J_max, J_sum) against
    their own input; ``annealed``/``refined2`` must never exceed
    ``refined:``'s J_max on ragged rows, and ``portfolio`` must be
    lexicographically no worse than ``annealed`` everywhere (its ladder 0
    reproduces the annealed run) at < K x the annealed wall-time on the
    ragged rows (batched ladders, shared schedule prefix).  A ``sharded``
    variant must never worsen (J_max, J_sum) vs ``annealed`` (structural:
    its ladder 0 replays the annealed ladder) and vs ``portfolio`` at
    matching K (bit-identity / adaptive superset); at larger K the claim
    is the K-scaling one — wall-time under 4x the single-process
    portfolio row despite the K_s/K_p-x ladder count.
    """
    claims = []
    if "refined" in variants:
        if objective == "j_max":
            worse = [r for r in rows
                     if not _lex_le(_key(r, "refined"), _key(r, "base"),
                                    _rtol(r))]
            label = "refined (J_max, J_sum) <= base"
        else:
            worse = [r for r in rows if r["j_sum_refined"] > r["j_sum_base"]
                     and not math.isclose(r["j_sum_refined"],
                                          r["j_sum_base"], rel_tol=_rtol(r))]
            label = "refined J_sum <= base"
        claims.append(("PASS" if not worse else "FAIL")
                      + f": {label} on all {len(rows)} rows"
                      + (f" (violations: {[(r['instance'], r['mapper']) for r in worse]})"
                         if worse else ""))
        key = "j_max" if objective == "j_max" else "j_sum"
        improved = [r for r in rows
                    if r["mapper"] == "random" and
                    r[f"{key}_refined"] < r[f"{key}_base"]]
        total_random = [r for r in rows if r["mapper"] == "random"]
        claims.append(("PASS" if len(improved) == len(total_random) else "FAIL")
                      + f": refinement improves random's {key} on "
                      f"{len(improved)}/{len(total_random)} instances")
    for variant in variants:
        prefix = variant_prefix(variant)
        if prefix == "refined":
            continue
        worse = [r for r in rows
                 if not _lex_le(_key(r, variant), _key(r, "base"), _rtol(r))]
        claims.append(("PASS" if not worse else "FAIL")
                      + f": {variant} (J_max, J_sum) <= base on all "
                      f"{len(rows)} rows"
                      + (f" (violations: {[(r['instance'], r['mapper']) for r in worse]})"
                         if worse else ""))
        # the "no worse than refined:" guarantee only holds when refined:
        # runs the schedule's own first phase (j_sum objective, matching
        # parameters) — under --objective j_max the comparison is apples
        # to oranges, so skip the claim rather than report a false FAIL.
        if "refined" in variants and objective == "j_sum" \
                and prefix not in ("portfolio", "sharded"):
            ragged = [r for r in rows if r["ragged"]]
            worse = [r for r in ragged
                     if r[f"j_max_{variant}"] > r["j_max_refined"]
                     and not math.isclose(r[f"j_max_{variant}"],
                                          r["j_max_refined"],
                                          rel_tol=_rtol(r))]
            claims.append(("PASS" if not worse else "FAIL")
                          + f": {variant} J_max <= refined J_max on all "
                          f"{len(ragged)} ragged-pod rows"
                          + (f" (violations: {[(r['instance'], r['mapper']) for r in worse]})"
                             if worse else ""))
    # portfolio vs annealed: dominance + batched wall-time
    port = [v for v in variants if variant_prefix(v) == "portfolio"]
    ann = [v for v in variants if variant_prefix(v) == "annealed"]
    if port and ann:
        pv, av = port[0], ann[0]
        pk = _portfolio_k(pv)
        worse = [r for r in rows
                 if not _lex_le(_key(r, pv), _key(r, av), _rtol(r))]
        claims.append(("PASS" if not worse else "FAIL")
                      + f": {pv} (J_max, J_sum) <= {av} on all {len(rows)} "
                      f"rows"
                      + (f" (violations: {[(r['instance'], r['stencil'], r['mapper']) for r in worse]})"
                         if worse else ""))
        # timing floor: rows whose single ladder finishes in < 0.5 s are
        # all fixed-overhead jitter (both sides are a few hundred numpy
        # calls, and a loaded box can double either), so the
        # batched-not-looped claim is checked where the measurement means
        # something.
        ragged = [r for r in rows if r["ragged"]
                  and r[f"t_{av}_s"] >= 0.5]
        skipped = sum(1 for r in rows if r["ragged"]
                      and r[f"t_{av}_s"] < 0.5)
        slow = [r for r in ragged if r[f"t_{pv}_s"] >= pk * r[f"t_{av}_s"]]
        claims.append(("PASS" if not slow else "FAIL")
                      + f": {pv} wall-time < k={pk} x {av} on all "
                      f"{len(ragged)} ragged-pod rows with {av} >= 0.5s "
                      f"({skipped} sub-0.5s rows skipped)"
                      + (f" (violations: {[(r['instance'], r['stencil'], r['mapper']) for r in slow]})"
                         if slow else ""))
    # sharded engine claims.  Quality: sharded's ladder 0 replays the
    # annealed ladder (through the portfolio engine it is bit-identical
    # to), so `sharded <= annealed` is structural on every row; vs
    # `portfolio` the guarantee is structural only at matching K
    # (bit-identity when adaptive control is off, superset candidates when
    # on) — across different Ks polish-set divergence makes it merely
    # likely, so no claim is stated.  Timing: the K-scaling claim — K_s
    # sharded starts must stay under 4x the K_p single-process row's
    # wall-time despite K_s/K_p-x the ladder count (batched ladders +
    # process sharding) — only means something when K_s > K_p; at equal K
    # sharding is pure overhead at benchmark sizes, so those rows are not
    # compared.
    shard = [v for v in variants if variant_prefix(v) == "sharded"]
    for sv in shard:
        sk = _portfolio_k(sv)
        if ann:
            av = ann[0]
            worse = [r for r in rows
                     if not _lex_le(_key(r, sv), _key(r, av), _rtol(r))]
            claims.append(("PASS" if not worse else "FAIL")
                          + f": {sv} (J_max, J_sum) <= {av} on all "
                          f"{len(rows)} rows"
                          + (f" (violations: {[(r['instance'], r['stencil'], r['mapper']) for r in worse]})"
                             if worse else ""))
        if port:
            pv = port[0]
            pk = _portfolio_k(pv)
            if sk == pk:
                worse = [r for r in rows
                         if not _lex_le(_key(r, sv), _key(r, pv), _rtol(r))]
                claims.append(("PASS" if not worse else "FAIL")
                              + f": {sv} (J_max, J_sum) <= {pv} on all "
                              f"{len(rows)} rows (matching K={sk}: "
                              "bit-identity / adaptive superset)"
                              + (f" (violations: {[(r['instance'], r['stencil'], r['mapper']) for r in worse]})"
                                 if worse else ""))
            else:
                # aggregate, not per-row: single-row wall-times at smoke
                # sizes are dominated by fixed overhead and machine-load
                # jitter, and the sum is what the K-scaling tradeoff is
                # about anyway
                t_s = sum(r[f"t_{sv}_s"] for r in rows)
                t_p = sum(r[f"t_{pv}_s"] for r in rows)
                ok = t_s < 4.0 * t_p
                claims.append(("PASS" if ok else "FAIL")
                              + f": {sv} (K={sk}) total wall-time "
                              f"{t_s:.1f}s < 4x {pv} (K={pk}) total "
                              f"{t_p:.1f}s over {len(rows)} rows "
                              f"({sk / pk:.0f}x the starts at "
                              f"{t_s / max(t_p, 1e-9):.1f}x the time)")
    # linksim replay: simulated bottleneck DCI must track J_max exactly
    sim_rows = [r for r in rows if "dci_max_base" in r]
    if sim_rows:
        bad = []
        for r in sim_rows:
            for suffix in ("base",) + tuple(variants):
                if f"dci_max_{suffix}" not in r:
                    continue
                if not math.isclose(r[f"dci_max_{suffix}"],
                                    r[f"j_max_{suffix}"],
                                    rel_tol=1e-9, abs_tol=1e-9):
                    bad.append((r["instance"], r["mapper"], suffix))
        n_ragged = sum(1 for r in sim_rows if r["ragged"])
        claims.append(("PASS" if not bad else "FAIL")
                      + f": linksim max_dci_pod == J_max on all "
                      f"{len(sim_rows)} rows ({n_ragged} ragged, replayed "
                      f"on per-pod torus sizes)"
                      + (f" (violations: {bad})" if bad else ""))
    return claims


# ---------------------------------------------------------------------------
# warm-start repair suite: repair-vs-cold on the churn scenarios
# (BENCH_6.json — wall-time, J_max/J_sum, repair-vs-cold ratios)

REPAIR_EPS = 0.05           # quality band vs the cold elastic portfolio
REPAIR_LATENCY_FRAC = 0.5   # repair wall-time cap as a fraction of cold


def _repair_stencil():
    """Byte-weighted ring (data-parallel traffic outweighing model-parallel
    — the ``stencil_for_plan`` shape) so the quality band is measured at
    the weighted granularity the runtime actually solves."""
    return Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)),
                   (3.0, 3.0, 1.0, 1.0), name="ring-w")


def repair_scenarios():
    """(label, prev_shape, prev_sizes, shape, sizes, node_map) — the three
    churn kinds the runtime produces: whole-pod loss (runtime-style
    ``(n, chips)`` re-mesh), pod rejoin, and a slow pod's down-weighted
    re-solve."""
    from repro.core.repair import downweighted_node_sizes
    return [
        ("loss-8to7", (8, 16), (16,) * 8, (7, 16), (16,) * 7,
         [0, 1, 2, 3, 4, 5, 7]),
        ("add-7to8", (7, 16), (16,) * 7, (8, 16), (16,) * 8,
         [0, 1, 2, 3, 4, 5, 6, -1]),
        ("slow-8", (8, 16), (16,) * 8, (8, 16),
         tuple(downweighted_node_sizes((16,) * 8, 3, 2.0)), None),
    ]


def run_repair():
    """One row per churn scenario: cold elastic-portfolio solve vs
    warm-start repair of the pre-churn solution (quality, wall-time,
    ratios, and the repair stage's own stats)."""
    from repro.core import (MappingProblem, elastic_portfolio_plan,
                            repair_layout)
    st = _repair_stencil()
    rows = []
    for label, pshape, psizes, shape, sizes, node_map in repair_scenarios():
        prev = elastic_portfolio_plan().solve(
            MappingProblem(tuple(pshape), st, tuple(psizes)))
        t0 = time.perf_counter()
        cold = elastic_portfolio_plan().solve(
            MappingProblem(tuple(shape), st, tuple(sizes)))
        t_cold = time.perf_counter() - t0
        rep, t_rep = None, float("inf")
        for _ in range(2):      # min-of-2: repair is deterministic, the
            t0 = time.perf_counter()    # clock is the only noisy part
            rep = repair_layout(prev, sizes, mesh_shape=shape,
                                node_map=node_map, cache=False)
            t_rep = min(t_rep, time.perf_counter() - t0)
        stats = rep.stage_stats[0]
        rows.append({
            "scenario": label,
            "prev_shape": list(pshape), "mesh_shape": list(shape),
            "node_sizes": [int(s) for s in sizes],
            "j_max_cold": cold.j_max, "j_sum_cold": cold.j_sum,
            "t_cold_s": t_cold,
            "j_max_repair": rep.j_max, "j_sum_repair": rep.j_sum,
            "t_repair_s": t_rep,
            "ratio_j_max": rep.j_max / cold.j_max,
            "ratio_j_sum": rep.j_sum / cold.j_sum,
            "latency_frac": t_rep / t_cold,
            "used_fallback": bool(stats.get("used_fallback")),
            "strategy": stats.get("strategy", "warm"),
            "swaps": stats.get("swaps"),
            "resplits": stats.get("resplits"),
            "pinned": stats.get("pinned"),
        })
    return rows


def validate_repair_claims(rows, eps=REPAIR_EPS, frac=REPAIR_LATENCY_FRAC):
    """The PR's acceptance bar, machine-checked: repair within ``eps`` of
    cold on both objectives, at most ``frac`` of cold's wall-time, and
    never via the silent cold fallback."""
    claims = []
    bad = [r for r in rows if r["ratio_j_max"] > 1 + eps
           or r["ratio_j_sum"] > 1 + eps]
    claims.append(("PASS" if not bad else "FAIL")
                  + f": repair within {eps:.0%} of cold (J_max and J_sum) "
                  f"on all {len(rows)} scenarios"
                  + (f" (violations: {[(r['scenario'], round(r['ratio_j_max'], 3), round(r['ratio_j_sum'], 3)) for r in bad]})"
                     if bad else ""))
    slow = [r for r in rows if r["latency_frac"] > frac]
    claims.append(("PASS" if not slow else "FAIL")
                  + f": repair wall-time <= {frac:.0%} of cold on all "
                  f"{len(rows)} scenarios"
                  + (f" (violations: {[(r['scenario'], round(r['latency_frac'], 2)) for r in slow]})"
                     if slow else ""))
    fb = [r for r in rows if r["used_fallback"]]
    claims.append(("PASS" if not fb else "FAIL")
                  + ": warm path taken on all scenarios (no cold fallback)"
                  + (f" (violations: {[r['scenario'] for r in fb]})"
                     if fb else ""))
    return claims


def print_repair_table(rows):
    print(f"{'scenario':12s} {'mesh':10s} "
          f"{'Jmax_cold':>9s} {'Jsum_cold':>9s} "
          f"{'Jmax_rep':>9s} {'Jsum_rep':>9s} "
          f"{'rmax':>6s} {'rsum':>6s} {'t_cold':>8s} {'t_rep':>8s} "
          f"{'frac':>5s}  strategy")
    for r in rows:
        shape = "x".join(str(d) for d in r["mesh_shape"])
        print(f"{r['scenario']:12s} {shape:10s} "
              f"{r['j_max_cold']:9.0f} {r['j_sum_cold']:9.0f} "
              f"{r['j_max_repair']:9.0f} {r['j_sum_repair']:9.0f} "
              f"{r['ratio_j_max']:6.3f} {r['ratio_j_sum']:6.3f} "
              f"{r['t_cold_s'] * 1e3:6.0f}ms {r['t_repair_s'] * 1e3:6.0f}ms "
              f"{r['latency_frac']:5.2f}  {r['strategy']}")


# ---------------------------------------------------------------------------
# device-resident portfolio suite: dominance at equal proposal budget +
# the K-scaling sweep (BENCH_7.json — J_max/J_sum vs the serial portfolio,
# starts-per-second at fixed budget)

#: Dominance config: both engines get the same K, schedule, and proposal
#: budget; the device's edge is structural (2K candidates incl. per-ladder
#: walk minima, polish over every unique survivor vs the host's top-3).
DEVICE_K = 32
DEVICE_MOVES = 40
DEVICE_BASES = ("hyperplane", "kdtree", "blocked", "random")
#: K-scaling sweep: ladder count at a fixed total proposal budget per
#: temperature (K x sa_moves held constant) — the paper's "more starts at
#: the same budget" lever, which only pays off if batching amortizes.
DEVICE_SWEEP_KS = (8, 64, 256, 1024)
DEVICE_SWEEP_BUDGET = 25600


def run_device():
    """Dominance rows: tiny refine-suite instances x base mappers,
    ``device[k=K,sa_moves=M,polish_top=none]:<base>`` against
    ``portfolio[k=K,sa_moves=M]:<base>`` at equal proposal budget
    (the pinned claim of ``tests/test_device_portfolio.py``, here over
    the full base-mapper matrix)."""
    spell_d = f"device[k={DEVICE_K},sa_moves={DEVICE_MOVES},polish_top=none]"
    spell_p = f"portfolio[k={DEVICE_K},sa_moves={DEVICE_MOVES}]"
    rows = []
    for label, dims, sizes in TINY_INSTANCES:
        grid = CartGrid(dims)
        stencil = Stencil.nearest_neighbor(grid.ndim)
        for base in DEVICE_BASES:
            row = {"instance": label, "base": base,
                   "k": DEVICE_K, "sa_moves": DEVICE_MOVES}
            for tag, spell in (("device", spell_d), ("portfolio", spell_p)):
                vm = get_mapper(f"{spell}:{base}")
                t0 = time.perf_counter()
                assign = vm.assignment(grid, stencil, sizes)
                t_total = time.perf_counter() - t0
                cost = evaluate(grid, stencil, assign, num_nodes=len(sizes))
                row[f"j_max_{tag}"] = cost.j_max
                row[f"j_sum_{tag}"] = cost.j_sum
                row[f"t_{tag}_s"] = t_total
                if tag == "device":
                    row["backend"] = vm.last_result.stats["backend"]
            rows.append(row)
    return rows


def run_device_sweep(ks=DEVICE_SWEEP_KS, budget=DEVICE_SWEEP_BUDGET):
    """One full temperature per K at a fixed proposal budget (jit warmed,
    min-of-3): wall-time, starts/s, proposals/s.  The lock-step vmapped
    kernel makes per-proposal cost roughly K-independent, so K=1024 must
    land under 4x the K=8 wall-time — more starts for the same budget."""
    from repro.core.refine import DeviceLadderEngine
    grid = CartGrid((8, 8))
    stencil = Stencil.nearest_neighbor(2)
    rng = np.random.default_rng(5)
    start = rng.permutation(np.repeat(np.arange(4), grid.size // 4))
    sweep = []
    for K in ks:
        moves = budget // K
        eng = DeviceLadderEngine(grid, stencil, start,
                                 seeds=tuple(range(K)), num_nodes=4)
        alive = np.ones(K, dtype=bool)
        temps, eps = np.full(K, 1.0), np.full(K, 1e-2)
        eng.run_temperature(temps, moves, alive, eps)        # jit compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            eng.run_temperature(temps, moves, alive, eps)
            best = min(best, time.perf_counter() - t0)
        sweep.append({"k": K, "sa_moves": moves, "proposals": K * moves,
                      "t_temp_s": best, "starts_per_s": K / best,
                      "proposals_per_s": K * moves / best})
    return sweep


def validate_device_claims(rows, sweep):
    """The PR's acceptance bar, machine-checked: device lexicographically
    never worse than the serial portfolio at equal budget on every row, no
    silent host fallback, and K=1024 under 4x the K=8 wall-time at fixed
    proposal budget."""
    claims = []
    worse = [r for r in rows
             if not _lex_le((r["j_max_device"], r["j_sum_device"]),
                            (r["j_max_portfolio"], r["j_sum_portfolio"]))]
    claims.append(("PASS" if not worse else "FAIL")
                  + f": device[k={DEVICE_K}] (J_max, J_sum) <= "
                  f"portfolio[k={DEVICE_K}] at equal proposal budget on all "
                  f"{len(rows)} rows"
                  + (f" (violations: {[(r['instance'], r['base']) for r in worse]})"
                     if worse else ""))
    fb = [r for r in rows if not r["backend"].startswith("device[")]
    claims.append(("PASS" if not fb else "FAIL")
                  + ": device path taken on all rows (no host fallback)"
                  + (f" (violations: {[(r['instance'], r['base'], r['backend']) for r in fb]})"
                     if fb else ""))
    t = {s["k"]: s["t_temp_s"] for s in sweep}
    lo, hi = min(t), max(t)
    ok = t[hi] < 4.0 * t[lo]
    claims.append(("PASS" if ok else "FAIL")
                  + f": K={hi} wall-time {t[hi] * 1e3:.0f}ms < 4x K={lo} "
                  f"({t[lo] * 1e3:.0f}ms) at {DEVICE_SWEEP_BUDGET} "
                  f"proposals/temperature ({hi // lo}x the starts at "
                  f"{t[hi] / t[lo]:.2f}x the time)")
    return claims


def print_device_table(rows, sweep):
    print(f"{'instance':14s} {'base':12s} "
          f"{'Jmax_dev':>8s} {'Jsum_dev':>8s} "
          f"{'Jmax_port':>9s} {'Jsum_port':>9s} "
          f"{'t_dev':>8s} {'t_port':>8s}  backend")
    for r in rows:
        print(f"{r['instance']:14s} {r['base']:12s} "
              f"{r['j_max_device']:8.0f} {r['j_sum_device']:8.0f} "
              f"{r['j_max_portfolio']:9.0f} {r['j_sum_portfolio']:9.0f} "
              f"{r['t_device_s'] * 1e3:6.0f}ms {r['t_portfolio_s'] * 1e3:6.0f}ms"
              f"  {r['backend']}")
    print()
    print(f"{'K':>5s} {'moves':>6s} {'proposals':>9s} {'t_temp':>8s} "
          f"{'starts/s':>9s} {'props/s':>10s}")
    for s in sweep:
        print(f"{s['k']:5d} {s['sa_moves']:6d} {s['proposals']:9d} "
              f"{s['t_temp_s'] * 1e3:6.0f}ms {s['starts_per_s']:9.0f} "
              f"{s['proposals_per_s']:10.0f}")


# ---------------------------------------------------------------------------
# hierarchical mapping suite (BENCH_8.json): multilevel quality at a
# fraction of the flat portfolio's cost on a deep 4096-chip machine, plus
# the depth sweep against the blocked baseline.

#: claim (a) instance: a 2-level machine of 256 pods x 16 chips (the
#: V5E_4RACK shape scaled out), 64x64 process grid.
HIER_BIG = ("2d-64x64-4096chips", (64, 64), [16] * 256, "16x16")
HIER_FLAT_SPELL = "portfolio[k=8]:hyperplane"
HIER_BIG_SPELL = "hier[fanouts=16x16]:hyperplane"
#: claim (a) bars: hier within 5% of the flat portfolio's J_max at <= 25%
#: of its wall-time.
HIER_JMAX_RATIO = 1.05
HIER_TIME_FRAC = 0.25
#: claim (b) instance + sweep: every tree depth must strictly beat the
#: blocked baseline on J_sum.
HIER_SWEEP = ("2d-32x32-1024chips", (32, 32), [16] * 64)
HIER_SWEEP_DEPTHS = (2, 3, 4)
HIER_SWEEP_SOLVER = "portfolio[k=4]"


def _hier_cold(spell, grid, stencil, sizes):
    """One cold solve: the subtree cache is cleared first so reported
    wall-times never ride on hits warmed by a previous variant."""
    from repro.core.refine import hier_subtree_cache
    hier_subtree_cache().clear()
    vm = get_mapper(spell)
    t0 = time.perf_counter()
    assign = vm.assignment(grid, stencil, sizes)
    t = time.perf_counter() - t0
    cost = evaluate(grid, stencil, assign, num_nodes=len(sizes))
    return assign, cost, t, vm


def run_hier_big():
    """Claim (a) rows: blocked / flat portfolio / hier on the 4096-chip
    instance, plus a warm hier re-solve (pure subtree-cache hits) to
    report the elastic re-mesh latency."""
    label, dims, sizes, fanouts = HIER_BIG
    grid = CartGrid(dims)
    stencil = Stencil.nearest_neighbor(grid.ndim)
    rows = []
    for tag, spell in (("blocked", "blocked"),
                       ("flat", HIER_FLAT_SPELL),
                       ("hier", HIER_BIG_SPELL)):
        _, cost, t, vm = _hier_cold(spell, grid, stencil, sizes)
        row = {"instance": label, "variant": tag, "spelling": spell,
               "j_max": cost.j_max, "j_sum": cost.j_sum, "t_s": t}
        if tag == "hier":
            stats = vm.last_result.stats
            row["solves"] = stats["solves"]
            row["fanouts"] = fanouts
            t0 = time.perf_counter()
            get_mapper(spell).assignment(grid, stencil, sizes)
            row["t_warm_s"] = time.perf_counter() - t0
        rows.append(row)
    return rows


def run_hier_sweep():
    """Claim (b) rows: ``hier[depth=d,solver=...]:blocked`` vs flat
    blocked at every tree depth."""
    label, dims, sizes = HIER_SWEEP
    grid = CartGrid(dims)
    stencil = Stencil.nearest_neighbor(grid.ndim)
    blocked = get_mapper("blocked").assignment(grid, stencil, sizes)
    ref = evaluate(grid, stencil, blocked, num_nodes=len(sizes))
    rows = []
    for depth in HIER_SWEEP_DEPTHS:
        spell = f"hier[depth={depth},solver={HIER_SWEEP_SOLVER}]:blocked"
        _, cost, t, _ = _hier_cold(spell, grid, stencil, sizes)
        rows.append({"instance": label, "depth": depth, "spelling": spell,
                     "j_max": cost.j_max, "j_sum": cost.j_sum, "t_s": t,
                     "j_max_blocked": ref.j_max, "j_sum_blocked": ref.j_sum})
    return rows


def validate_hier_claims(big, sweep):
    claims = []
    by = {r["variant"]: r for r in big}
    h, f = by["hier"], by["flat"]
    r_jmax = h["j_max"] / f["j_max"]
    r_time = h["t_s"] / f["t_s"]
    ok = r_jmax <= HIER_JMAX_RATIO and r_time <= HIER_TIME_FRAC
    claims.append(("PASS" if ok else "FAIL")
                  + f": {HIER_BIG_SPELL} reaches J_max <= "
                  f"{HIER_JMAX_RATIO:.2f}x of {HIER_FLAT_SPELL} at <= "
                  f"{HIER_TIME_FRAC:.0%} of its wall-time on "
                  f"{HIER_BIG[0]} (J_max ratio {r_jmax:.3f}, "
                  f"time ratio {r_time:.3f})")
    bad = [r for r in sweep if not r["j_sum"] < r["j_sum_blocked"]]
    claims.append(("PASS" if not bad else "FAIL")
                  + f": hier strictly beats flat blocked on J_sum at every "
                  f"depth in {list(HIER_SWEEP_DEPTHS)} on {HIER_SWEEP[0]}"
                  + (f" (violations: {[(r['depth'], r['j_sum']) for r in bad]})"
                     if bad else ""))
    return claims


def print_hier_table(big, sweep):
    print(f"{'variant':8s} {'spelling':42s} {'J_max':>6s} {'J_sum':>7s} "
          f"{'t':>8s} {'t_warm':>8s}")
    for r in big:
        warm = f"{r['t_warm_s']:7.2f}s" if "t_warm_s" in r else f"{'-':>8s}"
        print(f"{r['variant']:8s} {r['spelling']:42s} {r['j_max']:6.0f} "
              f"{r['j_sum']:7.0f} {r['t_s']:7.2f}s {warm}")
    print()
    print(f"{'depth':5s} {'spelling':42s} {'J_max':>6s} {'J_sum':>7s} "
          f"{'Jsum_blk':>8s} {'t':>8s}")
    for r in sweep:
        print(f"{r['depth']:<5d} {r['spelling']:42s} {r['j_max']:6.0f} "
              f"{r['j_sum']:7.0f} {r['j_sum_blocked']:8.0f} "
              f"{r['t_s']:7.2f}s")


def _portfolio_k(variant):
    m = re.search(r"\bk=(\d+)", variant)
    if m:
        return int(m.group(1))
    from repro.core import PortfolioRefiner
    return PortfolioRefiner().k


_SHORT = {"refined": "ref", "refined2": "ref2", "annealed": "ann",
          "portfolio": "port", "sharded": "shrd"}


def _short(variant):
    return _SHORT.get(variant_prefix(variant), variant_prefix(variant)[:4])


def print_table(rows, variants=VARIANTS):
    short = [_short(v) for v in variants]
    cols = "".join(f" {'Jsum_' + s:>9s} {'Jmax_' + s:>9s}" for s in short)
    times = "".join(f" {'t_' + s:>9s}" for s in short)
    print(f"{'instance':18s} {'stencil':8s} {'mapper':15s} "
          f"{'J_sum':>9s} {'J_max':>7s}{cols}{times}")
    for r in rows:
        v_cols = "".join(f" {r[f'j_sum_{v}']:9.0f} {r[f'j_max_{v}']:9.0f}"
                         for v in variants)
        v_times = "".join(f" {r[f't_{v}_s'] * 1e3:7.1f}ms" for v in variants)
        print(f"{r['instance']:18s} {r['stencil']:8s} {r['mapper']:15s} "
              f"{r['j_sum_base']:9.0f} {r['j_max_base']:7.0f}"
              f"{v_cols}{v_times}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="smoke subset")
    ap.add_argument("--mappers", default=None,
                    help="comma list (default: all registered)")
    ap.add_argument("--variants", default=",".join(VARIANTS),
                    help="comma list of refinement prefixes to compare "
                         "(bracket options allowed, e.g. portfolio[k=8])")
    ap.add_argument("--stencils", default=None,
                    help="comma list of stencil keys "
                         f"(default: all of {sorted(STENCILS)})")
    ap.add_argument("--instances", default=None,
                    help="substring filter on instance labels "
                         "(e.g. 'ragged')")
    ap.add_argument("--linksim", action="store_true",
                    help="replay every row through analysis.linksim (ragged "
                         "rows on per-pod torus sizes) and add dci_max "
                         "columns + the J_max==dci claim")
    ap.add_argument("--policy", default="first",
                    choices=["first", "steepest"])
    ap.add_argument("--objective", default="j_sum",
                    choices=["j_sum", "j_max"],
                    help="refined: objective (scheduled variants own theirs)")
    ap.add_argument("--repair", action="store_true",
                    help="run the warm-start repair suite instead of the "
                         "variant sweep (repair-vs-cold on loss/add/slow "
                         "churn scenarios; --json emits the BENCH_6.json "
                         "rows)")
    ap.add_argument("--device", action="store_true",
                    help="run the device-portfolio suite instead of the "
                         "variant sweep (dominance vs the serial portfolio "
                         "at equal proposal budget + the K-scaling sweep; "
                         "--json emits the BENCH_7.json payload)")
    ap.add_argument("--hier", action="store_true",
                    help="run the hierarchical mapping suite instead of the "
                         "variant sweep (hier-vs-flat-portfolio on a "
                         "4096-chip 2-level machine + the depth sweep vs "
                         "blocked; --json emits the BENCH_8.json payload)")
    ap.add_argument("--json", default=None, help="also dump rows as JSON")
    args = ap.parse_args()
    enable_compile_cache()

    if args.hier:
        big = run_hier_big()
        sweep = run_hier_sweep()
        print_hier_table(big, sweep)
        print()
        claims = validate_hier_claims(big, sweep)
        for c in claims:
            print("# " + c)
        if args.json:
            with open(args.json, "w") as f:
                json.dump({"big": big, "depth_sweep": sweep,
                           "claims": claims}, f, indent=1, default=float)
        if any(c.startswith("FAIL") for c in claims):
            raise SystemExit(1)
        return

    if args.device:
        rows = run_device()
        sweep = run_device_sweep()
        print_device_table(rows, sweep)
        print()
        claims = validate_device_claims(rows, sweep)
        for c in claims:
            print("# " + c)
        if args.json:
            with open(args.json, "w") as f:
                json.dump({"dominance": rows, "k_scaling": sweep,
                           "claims": claims}, f, indent=1, default=float)
        if any(c.startswith("FAIL") for c in claims):
            raise SystemExit(1)
        return

    if args.repair:
        rows = run_repair()
        print_repair_table(rows)
        print()
        claims = validate_repair_claims(rows)
        for c in claims:
            print("# " + c)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rows, f, indent=1, default=float)
        if any(c.startswith("FAIL") for c in claims):
            raise SystemExit(1)
        return

    variants = split_variants(args.variants)
    rows = run(tiny=args.tiny,
               mappers=args.mappers.split(",") if args.mappers else None,
               variants=variants,
               stencils=args.stencils.split(",") if args.stencils else None,
               instances=args.instances,
               linksim=args.linksim,
               refine_kwargs={"policy": args.policy,
                              "objective": args.objective})
    print_table(rows, variants=variants)
    print()
    claims = validate_claims(rows, objective=args.objective,
                             variants=variants)
    for c in claims:
        print("# " + c)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1, default=float)
    if any(c.startswith("FAIL") for c in claims):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
