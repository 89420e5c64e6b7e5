"""Swaps the polish applied per pair it scored, over the solved requests:
the mean of the refiner's ``survivors/polish/swap.applied`` counter over
the mean of ``survivors/polish/swap.pairs`` (a pass that applied nothing
adds no ``swap.applied``), the share of the scoring that was useful
(layer: refine polish)."""

PAIRS = "survivors/polish/swap.pairs"
APPLIED = "survivors/polish/swap.applied"


def read(run):
    pairs = applied = 0
    for r in run.solved():
        counters = (r["solution"]["engine_stage"] or {}).get("counters") or {}
        if PAIRS in counters:
            pairs += counters[PAIRS]
            applied += counters.get(APPLIED, 0)
    return applied / pairs if pairs else None
