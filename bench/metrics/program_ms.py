"""Device time of the sweep's programs per sweep: the summed runs of the
jitted programs (``XLA Modules`` events) in the window, over the sweeps."""

LAYER = "runtime.vector_backend sweep program"
UNIT = "ms"
MOVES = "sim_tasks_per_s"


def read(run):
    tr = run.trace
    if tr is None or tr.modules == 0 or run.sweeps == 0:
        return None
    return 1e3 * tr.module_s / run.sweeps
