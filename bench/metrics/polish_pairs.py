"""Mean swap pairs scored by the polish per solved request, from the
refiner's own ``survivors/polish/swap.pairs`` counter: the work the
polish's scoring does (layer: refine polish)."""

PATH = "survivors/polish/swap.pairs"


def read(run):
    vals = []
    for r in run.solved():
        counters = (r["solution"]["engine_stage"] or {}).get("counters") or {}
        if PATH in counters:
            vals.append(counters[PATH])
    return sum(vals) / len(vals) if vals else None
