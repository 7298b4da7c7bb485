"""Host time per sweep spent generating the seeds' workloads: the
program's ``repro.batched.generate`` spans in the window, over the
sweeps."""

from bench import spans

LAYER = "lab.backends host lowering and jit"
UNIT = "ms"
MOVES = "sim_tasks_per_s"


def read(run):
    return spans.span_ms_per_sweep(run, "repro.batched.generate")
