"""How full the device scorer's chunks are in the polish, over the solved
requests: the refiner's ``survivors/polish/swap.device_pairs`` counter
over its ``survivors/polish/swap.device_slots`` (the pair slots of the
whole chunks each scored pass dispatched).  1 - fill is the share of the
scorer's device work spent on padding.  Nothing where the scorer did not
run, or for a program that has no slot counter (layer: refine polish)."""

PAIRS = "survivors/polish/swap.device_pairs"
SLOTS = "survivors/polish/swap.device_slots"


def read(run):
    pairs = slots = 0
    for r in run.solved():
        counters = (r["solution"]["engine_stage"] or {}).get("counters") or {}
        if SLOTS in counters:
            pairs += counters.get(PAIRS, 0)
            slots += counters[SLOTS]
    return pairs / slots if slots else None
