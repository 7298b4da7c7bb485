"""Host time per sweep spent laying the tasks out as (seeds, slots, K)
rows: the program's ``repro.vector.layout`` spans in the window, over the
sweeps."""

from bench import spans

LAYER = "runtime.vector_backend sweep program"
UNIT = "ms"
MOVES = "sim_tasks_per_s"


def read(run):
    return spans.span_ms_per_sweep(run, "repro.vector.layout")
