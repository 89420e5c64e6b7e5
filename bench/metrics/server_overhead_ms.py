"""Client-side latency minus the plan's own solve time, mean per solved
request: the admission queue, the cache lookup, the server thread's
hand-off and the ticket (layer: serving)."""


def read(run):
    solved = run.solved()
    if not solved:
        return None
    gaps = [r["latency_s"] - r["solution"]["wall_time_s"] for r in solved]
    return 1e3 * sum(gaps) / len(gaps)
