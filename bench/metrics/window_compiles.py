"""Programs JAX built inside the measured window, compiled or loaded
from the persistent cache, from its ``backend_compile_duration`` events.
Set-up warms every shape, so it should read 0 (layer: compile cache)."""


def read(run):
    return run.window_compiles
