"""Mean seconds per solved request of the temperature boundaries, from the
refiner's own ``ladders/temperature/boundary`` span, summed over the
temperatures: the controller's best-seen, kill, alive mask and adapt,
restart spawns included (layer: refine boundary)."""

PATH = "ladders/temperature/boundary"


def read(run):
    vals = []
    for r in run.solved():
        spans = (r["solution"]["engine_stage"] or {}).get("spans") or {}
        if PATH in spans:
            vals.append(spans[PATH][1])
    return sum(vals) / len(vals) if vals else None
