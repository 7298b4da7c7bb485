"""Host time per sweep spent handling the scenario specs (the uniformity
checks and the results' fingerprints): the program's ``repro.lab.specs``
spans in the window, over the sweeps. A program without the span gives
nothing to read."""

from bench import spans

LAYER = "lab.api and lab.specs scenario handling"
UNIT = "ms"
MOVES = "sim_tasks_per_s"


def read(run):
    return spans.span_ms_per_sweep(run, "repro.lab.specs")
