"""Share of the prefix-scan kernel's roofline: the least time its useful
bytes need at the HBM peak, over the kernel's device time in the window.
Bytes: 8 per task (``costs.prefix_scan_bytes``); padding is not counted."""

from bench import costs

LAYER = "kernels.prefix_scan"
UNIT = "%"
MOVES = "sim_tasks_per_s"
KERNEL = "prefix_scan_pallas"


def read(run):
    tr = run.trace
    secs = tr.kernel_s.get(KERNEL, 0.0) if tr is not None else 0.0
    if secs <= 0.0:
        return None
    least = costs.least_seconds(costs.prefix_scan_bytes(run.tasks),
                                run.device_kind)
    return 100.0 * least / secs
