"""Mean seconds per solved request that the polish spends building swap
frontiers and scoring them, from the refiner's own
``survivors/polish/swap.score`` span, summed over the passes (layer:
refine polish)."""

PATH = "survivors/polish/swap.score"


def read(run):
    vals = []
    for r in run.solved():
        spans = (r["solution"]["engine_stage"] or {}).get("spans") or {}
        if PATH in spans:
            vals.append(spans[PATH][1])
    return sum(vals) / len(vals) if vals else None
