"""Mean seconds per solved request of fetching the survivors and keying
them exactly, from the refiner's own ``survivors/snapshot`` and
``survivors/rekey`` spans: the end-of-run fetch, the stacked crossing
counts and the portfolio cost (layer: survivors' rekeying)."""

PATHS = ("survivors/snapshot", "survivors/rekey")


def read(run):
    vals = []
    for r in run.solved():
        spans = (r["solution"]["engine_stage"] or {}).get("spans") or {}
        if all(p in spans for p in PATHS):
            vals.append(sum(spans[p][1] for p in PATHS))
    return sum(vals) / len(vals) if vals else None
