"""Mean seconds per solved request that the polish spends building its
swap passes' frontiers (the candidate pairs each pass scores), from the
refiner's own ``survivors/polish/swap.score/swap.frontier`` span, summed
over the passes: the host's share of ``polish_score_s``.  Nothing for a
program that has no such span (layer: refine polish)."""

PATH = "survivors/polish/swap.score/swap.frontier"


def read(run):
    vals = []
    for r in run.solved():
        spans = (r["solution"]["engine_stage"] or {}).get("spans") or {}
        if PATH in spans:
            vals.append(spans[PATH][1])
    return sum(vals) / len(vals) if vals else None
