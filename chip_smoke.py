#!/usr/bin/env python3
"""On-chip smoke run of the mapping service's device path.

Drives the service once through the entry points a user calls, on a TPU,
and checks every answer against the repository's own references:

  (a) device      JAX must find a TPU; anything else exits non-zero.
  (b) stencil     the Pallas stencil kernel (``stencil_apply``) on a
                  2048x2048 f32 Jacobi shard plus halo, 5-point and halo-2,
                  compiled as a Mosaic ``tpu_custom_call`` and equal to
                  ``stencil_ref``.
  (c) cold solve  ``device[k=1024,restarts=auto]:hyperplane`` on a (64, 64)
                  mesh over 256 pods of 16 chips, through ``PlanServer`` /
                  ``PlanClient`` with a fresh ``PlanCache``: the device
                  backend ran, pod sizes hold, (J_max, J_sum) equal an
                  ``evaluate`` recount and are no worse than the hyperplane
                  base, and the engine's integer count state equals the
                  numpy recount.
  (d) warm hit    the same request again comes back from the cache with an
                  identical layout.
  (e) repair      ``repair_layout`` through the same server after one pod
                  drops to 12 chips (the repair stage runs on the host).
  (f) warm-up     ``PlanServer.warm_up()`` with the default serve plan,
                  whose shard workers start after the chip is in use.

With ``--chips 4`` it runs only the four-chip path instead: the device
order ``cart_create((2, 2), chips_per_pod=2)`` gives a ``Mesh``
(hyperplane, blocked and stencil_strips layouts), a ``shard_map`` Jacobi
with ``ppermute`` halo exchange on each checked against the single-array
oracle, and the mesh's device order checked against the layout.

Usage (from the root of a checkout, on a TPU host):

    python3 chip_smoke.py
    python3 chip_smoke.py --chips 4

Earlier lines print what is worth seeing (device kind, per-phase wall and
compile seconds, the device solve's time split); they are one-off smoke
readings, not measurements.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

#: (b) the per-chip Jacobi shard
SHARD = 2048
#: PRNG seed of the random stencil and Jacobi inputs
SEED = 0
#: (c) the fleet-size cold solve: a (64, 64) mesh over 256 pods of 16 chips
SOLVE_MESH = (64, 64)
SOLVE_PODS = (16,) * 256
SOLVE_PLAN = "device[k=1024,restarts=auto]:hyperplane"
#: (e) the churn: the last pod drops to 12 chips, so 4092 chips re-mesh
REPAIR_PODS = (16,) * 255 + (12,)
REPAIR_MESH = (62, 66)
#: seconds a served request may take before the smoke gives up on it
REQUEST_TIMEOUT_S = 900.0


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds and count of XLA backend compiles while it is open, read
    from JAX's own compile events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def _listen(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def __enter__(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


def check(cond: bool, what: str) -> None:
    """Fail the phase (raise) unless ``cond``; ``assert`` would vanish
    under ``python -O``."""
    if not cond:
        raise RuntimeError(f"check failed: {what}")


# ---------------------------------------------------------------------------
# (a) device


def find_tpu(chips: int) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX found {dev.platform!r} "
                         f"devices ({dev.device_kind}); this smoke runs "
                         "only on a TPU")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPUs, "
                         f"JAX found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# (b) the Pallas stencil kernel


def phase_stencil(n: int = SHARD) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import Stencil
    from repro.kernels.stencil.jacobi import jacobi_taps
    from repro.kernels.stencil.ops import stencil_apply, stencil_ref
    ref_fn = jax.jit(stencil_ref, static_argnums=(1, 2, 3))
    for name, st in (("5pt", Stencil.nearest_neighbor(2)),
                     ("halo2", Stencil.nn_with_hops(2, hops=(2,)))):
        offsets, weights, halo = jacobi_taps(st)
        u = jax.random.normal(jax.random.PRNGKey(SEED),
                              (n + 2 * halo, n + 2 * halo), jnp.float32)
        t0 = time.perf_counter()
        compiled = stencil_apply.lower(u, offsets, weights, halo).compile()
        t_compile = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: stencil_apply compiled without a Pallas TPU kernel")
        got = np.asarray(compiled(u))
        want = np.asarray(ref_fn(u, offsets, weights, halo))
        check(got.shape == (n, n), f"{name}: output shape {got.shape}")
        check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
        err = float(np.abs(got - want).max())
        check(err <= 1e-5, f"{name}: max|kernel - stencil_ref| = {err}")
        log(f"[stencil] {name}: {n}x{n} f32 halo={halo} tpu_custom_call "
            f"max|err| vs stencil_ref = {err:.3e} compile_s={t_compile:.3f}")


# ---------------------------------------------------------------------------
# (c)-(f) the served mapping path


def _engine_stage(sol) -> dict:
    """The stats of the last stage that reports the engine it ran on."""
    for st in reversed(sol.stage_stats):
        if "backend" in st:
            return st
    raise RuntimeError(f"no stage of {sol.plan_key} reports its backend")


def _key(sol) -> tuple:
    return float(sol.j_max), float(sol.j_sum)


def phase_cold_solve(client, platform: str, mesh=SOLVE_MESH,
                     pods=SOLVE_PODS, plan=SOLVE_PLAN) -> dict:
    import numpy as np
    from repro.core import CartGrid, Stencil, evaluate, parse_plan
    from repro.core.plan import MappingProblem
    st = Stencil.nearest_neighbor(len(mesh))
    ticket = client.cart_create_async(mesh, st, node_sizes=pods, plan=plan)
    cart = ticket.result(REQUEST_TIMEOUT_S)
    sol = cart.solution
    check(not cart.from_cache, "the cold solve came from the cache")
    stats = _engine_stage(sol)
    backend = stats["backend"]
    check(backend == f"device[{platform}]",
          f"device stage ran on {backend!r}, not device[{platform}]")
    counts = np.bincount(sol.assignment, minlength=len(pods))
    check(counts.tolist() == list(pods), "pod sizes not preserved")
    grid = CartGrid(mesh)
    cost = evaluate(grid, st, sol.assignment, num_nodes=len(pods))
    check((cost.j_max, cost.j_sum) == (sol.j_max, sol.j_sum),
          f"served (J_max, J_sum) = {(sol.j_max, sol.j_sum)} but the "
          f"recount gives {(cost.j_max, cost.j_sum)}")
    base = parse_plan("hyperplane").solve(MappingProblem(mesh, st, pods))
    check((sol.j_max, sol.j_sum) <= (base.j_max, base.j_sum),
          f"device result {(sol.j_max, sol.j_sum)} is worse than the "
          f"hyperplane base {(base.j_max, base.j_sum)}")
    log(f"[cold-solve] {plan} on {mesh} / {len(pods)} pods: "
        f"backend={backend} (J_max, J_sum)={_key(sol)} == evaluate "
        f"recount; hyperplane base={_key(base)}; "
        f"t_rounds_s={stats['t_rounds_s']:.3f} "
        f"t_ladders_s={stats['t_ladders_s']:.3f} "
        f"t_polish_s={stats['t_polish_s']:.3f} "
        f"latency_s={ticket.latency_s:.3f}")
    return {"cart": cart, "base": base}


def phase_count_state(base_assignment, mesh=SOLVE_MESH, pods=SOLVE_PODS,
                      k: int = 1024, sa_moves: int = 200) -> None:
    """One temperature of the device engine at the cold solve's shapes
    (K ladders plus K restart slots), then its resident integer count state
    against the numpy recount of the assignments it holds."""
    import numpy as np
    from repro.core import CartGrid, Stencil
    from repro.core.cost_delta import neighbor_table, stacked_count_arrays
    from repro.core.refine.device import DeviceLadderEngine
    grid, st = CartGrid(mesh), Stencil.nearest_neighbor(len(mesh))
    eng = DeviceLadderEngine(grid, st, base_assignment, seeds=range(k),
                             num_nodes=len(pods), restart_slots=k)
    rows = eng.rows
    eps = 1.0 / (1.0 + eng.start_key[1])
    rep = eng.run_temperature(np.full(rows, 2.0), sa_moves,
                              np.ones(k, dtype=bool), np.full(rows, eps))
    snap = eng.snapshot()
    _, cn = stacked_count_arrays(neighbor_table(grid, st), snap["nodes"],
                                 len(pods))
    check(np.array_equal(cn, snap["counts"]),
          "device count state differs from the numpy recount")
    check(np.array_equal(rep.j_max, cn.sum(axis=2).max(axis=1)),
          "device J_max keys differ from the recount")
    for r in range(rows):
        check(np.bincount(snap["nodes"][r], minlength=len(pods)).tolist()
              == list(pods), f"ladder {r} broke the pod sizes")
    log(f"[count-state] {rows} ladders x {sa_moves} moves: accepted="
        f"{int(rep.accepted.sum())}; integer count state == numpy "
        "stacked_count_arrays recount")


def phase_warm_hit(client, cold, mesh=SOLVE_MESH, pods=SOLVE_PODS,
                   plan=SOLVE_PLAN) -> None:
    import numpy as np
    from repro.core import Stencil
    ticket = client.cart_create_async(mesh,
                                      Stencil.nearest_neighbor(len(mesh)),
                                      node_sizes=pods, plan=plan)
    cart = ticket.result(REQUEST_TIMEOUT_S)
    check(cart.from_cache, "the repeated request missed the cache")
    check(np.array_equal(cart.layout, cold["cart"].layout),
          "the cached layout differs from the cold one")
    log(f"[warm-hit] from_cache=True identical layout "
        f"latency_s={ticket.latency_s:.6f}")


def phase_repair(client, cold, pods=REPAIR_PODS, mesh=REPAIR_MESH) -> None:
    import numpy as np
    from repro.core import CartGrid, evaluate
    prev = cold["cart"]
    t0 = time.perf_counter()
    sol = client.repair_async(prev, pods, mesh_shape=mesh).result(
        REQUEST_TIMEOUT_S)
    wall = time.perf_counter() - t0
    counts = np.bincount(sol.assignment, minlength=len(pods))
    check(counts.tolist() == list(pods), "repair broke the pod sizes")
    cost = evaluate(CartGrid(mesh), prev.problem.stencil, sol.assignment,
                    num_nodes=len(pods))
    check((cost.j_max, cost.j_sum) == (sol.j_max, sol.j_sum),
          "repaired (J_max, J_sum) differ from the recount")
    for st in sol.stage_stats:
        log(f"[repair] stage {st['stage'].split('[')[0]}: "
            f"backend={st.get('backend', 'unreported')} "
            f"used_fallback={st.get('used_fallback')}")
    log(f"[repair] pod {len(pods) - 1} -> {pods[-1]} chips, re-meshed "
        f"{mesh}: (J_max, J_sum)={_key(sol)} == recount wall_s={wall:.3f}")


def phase_warm_up(server) -> None:
    swept = server.warm_up()
    check(swept["swept"] > 0, "warm-up swept no topology")
    for name, backend in swept["backends"].items():
        log(f"[warm-up] {name}: {server.default_plan} backend={backend}")
        check(backend == "resident",
              f"{name}: default plan ran on {backend!r}, not the resident "
              "shard workers")
    ipc = server.stats()["ipc"]
    check(ipc["messages"] > 0, "no message reached a shard worker")
    log(f"[warm-up] swept={swept['swept']} shard worker messages="
        f"{ipc['messages']}")


# ---------------------------------------------------------------------------
# the four-chip path


def phase_mesh4(n: int = 2 * SHARD, iters: int = 8) -> None:
    """A 2x2 mesh of 2048x2048 shards (the phase (b) shard per chip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import cart_create
    from repro.kernels.stencil.jacobi import (distributed_jacobi,
                                              jacobi_oracle)
    devices = jax.devices()[:4]
    u0 = jax.random.normal(jax.random.PRNGKey(SEED), (n, n), jnp.float32)
    want = jacobi_oracle(u0, iters)
    # stencil_strips beside them: on a 2x2 mesh both give the identity
    # order, and a permuted one is what the order check is for
    for plan in ("hyperplane", "blocked", "stencil_strips"):
        cart = cart_create((2, 2), chips_per_pod=2, plan=plan, cache=False)
        mesh = cart.mesh(devices)
        order = [devices[i] for i in cart.layout.reshape(-1)]
        check(list(mesh.devices.reshape(-1)) == order,
              f"{plan}: mesh device order is not jax.devices() permuted "
              "by the layout")
        got = distributed_jacobi(mesh, u0, iters)
        err = float(np.abs(got - want).max())
        check(err < 1e-4, f"{plan}: max|distributed - oracle| = {err}")
        log(f"[mesh4] {plan}: layout={cart.layout.tolist()} mesh ids="
            f"{[d.id for d in mesh.devices.reshape(-1)]} "
            f"(J_max, J_sum)={_key(cart)} Jacobi x{iters} {n}x{n} "
            f"max|err| vs oracle = {err:.3e}")


# ---------------------------------------------------------------------------


def run_phase(name: str, fn, *args, **kwargs):
    with CompileClock() as clock:
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
    log(f"[{name}] passed wall_s={wall:.3f} compile_s={clock.seconds:.3f} "
        f"compiles={clock.count}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mapped-mesh path")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC / 'repro'} not found: run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    device = find_tpu(args.chips)
    log(f"[device] {device['kind']} x{device['count']} "
        f"(platform {device['platform']}); compile cache {cache_dir}")
    if args.chips == 4:
        run_phase("mesh4", phase_mesh4)
    else:
        from repro.core import PlanCache
        from repro.serving import PlanClient, PlanServer
        run_phase("stencil", phase_stencil)
        with PlanServer(cache=PlanCache(maxsize=64), threads=1) as server:
            client = PlanClient(server)
            cold = run_phase("cold-solve", phase_cold_solve, client,
                             device["platform"])
            run_phase("count-state", phase_count_state,
                      cold["base"].assignment)
            run_phase("warm-hit", phase_warm_hit, client, cold)
            run_phase("repair", phase_repair, client, cold)
            run_phase("warm-up", phase_warm_up, server)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
