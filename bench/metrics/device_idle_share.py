"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / (window)."""

LAYER = "device"
UNIT = "%"
MOVES = "sim_tasks_per_s"


def read(run):
    tr = run.trace
    if tr is None or tr.chips == 0 or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
