"""Share of the per-slot layout's lanes that hold a task: the ``tasks``
over the ``lanes`` (seeds x slots x K) that the program's
``repro.vector.layout`` spans count in the window."""

LAYER = "runtime.vector_backend sweep program"
UNIT = "%"
MOVES = "sim_tasks_per_s"


def read(run):
    sp = run.spans
    args = sp.span_args.get("repro.vector.layout", {}) if sp else {}
    if not args.get("lanes"):
        return None
    return 100.0 * args["tasks"] / args["lanes"]
