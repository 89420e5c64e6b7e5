"""Mean seconds per solved request that the polish spends applying scored
swaps, from the refiner's own ``survivors/polish/swap.apply`` span, summed
over the passes (layer: refine polish)."""

PATH = "survivors/polish/swap.apply"


def read(run):
    vals = []
    for r in run.solved():
        spans = (r["solution"]["engine_stage"] or {}).get("spans") or {}
        if PATH in spans:
            vals.append(spans[PATH][1])
    return sum(vals) / len(vals) if vals else None
