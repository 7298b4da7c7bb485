"""Device time per sweep of the positional rule's owner lookup: the own
time of the ops whose instructions sit in the program's ``owner_lookup``
scope, in the window, over the sweeps."""

LAYER = "runtime.vector_backend sweep program"
UNIT = "ms"
MOVES = "sim_tasks_per_s"


def read(run):
    sp = run.spans
    if sp is None or run.sweeps == 0 or "owner_lookup" not in sp.scope_s:
        return None
    return 1e3 * sp.scope_s["owner_lookup"] / run.sweeps
