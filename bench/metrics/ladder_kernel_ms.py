"""Device time of the temperature kernel per solved request, from the
trace: the summed durations of the program runs (``XLA Modules`` events)
that execute inside the benchmark's span around each
``DeviceLadderEngine.run_temperature`` call, so the reading does not
depend on the kernel's name (layer: device kernel)."""

SPAN = "bench.ladder_temperature"


def read(run):
    if run.trace is None or not run.solved():
        return None
    spans = run.trace.spans_named(SPAN)
    ns = run.trace.module_ns_within(spans)
    if not spans or ns <= 0:
        return None
    return ns / 1e6 / len(run.solved())
