"""Mean seconds per solved request of the device engine's set-up, from the
refiner's own ``ladders/engine_init`` span: building the resident state
and the ladders' keys before the first temperature (layer: refine engine
set-up)."""

PATH = "ladders/engine_init"


def read(run):
    vals = []
    for r in run.solved():
        spans = (r["solution"]["engine_stage"] or {}).get("spans") or {}
        if PATH in spans:
            vals.append(spans[PATH][1])
    return sum(vals) / len(vals) if vals else None
