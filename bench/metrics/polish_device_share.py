"""Share of the polish's swap pairs scored on the device, over the solved
requests: the refiner's ``survivors/polish/swap.device_pairs`` counter
over its ``survivors/polish/swap.pairs``.  It reads 1 where every pass of
the polish scored its pairs on the chip, 0 where none did, and nothing
for a program that has no such counter (layer: refine polish)."""

PAIRS = "survivors/polish/swap.pairs"
DEVICE = "survivors/polish/swap.device_pairs"


def read(run):
    pairs = device = 0
    found = False
    for r in run.solved():
        counters = (r["solution"]["engine_stage"] or {}).get("counters") or {}
        if PAIRS in counters:
            pairs += counters[PAIRS]
            device += counters.get(DEVICE, 0)
            found = found or DEVICE in counters
    return device / pairs if found and pairs else None
