"""Mean ``t_polish_s`` per solved request, from the device refine stage's
stats: the survivors' exact rekeying and the host polish (layer: refine
polish)."""


def read(run):
    vals = [r["solution"]["engine_stage"]["t_polish_s"]
            for r in run.solved()
            if (r["solution"]["engine_stage"] or {}).get("t_polish_s")
            is not None]
    return sum(vals) / len(vals) if vals else None
