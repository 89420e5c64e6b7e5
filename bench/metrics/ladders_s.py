"""Mean ``t_ladders_s`` per solved request, from the device refine
stage's stats: every temperature's scan on the device and its host
boundary round-trip (layer: refine ladders)."""


def read(run):
    vals = [r["solution"]["engine_stage"]["t_ladders_s"]
            for r in run.solved()
            if (r["solution"]["engine_stage"] or {}).get("t_ladders_s")
            is not None]
    return sum(vals) / len(vals) if vals else None
