"""Programs compiled, or loaded from the persistent compilation cache,
inside the window, counted by a ``jax.monitoring`` listener. The set-up
warms every shape the window meets, so this should read 0."""

LAYER = "lab.backends host lowering and jit"
UNIT = "count"
MOVES = "sim_tasks_per_s"


def read(run):
    return float(run.compiles)
