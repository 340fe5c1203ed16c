"""device_idle_share: the share of the traced window in which no operation
ran on the device, from the profiler's trace: 1 - (union of the device
operations' intervals) / (traced window)."""


def read(run):
    red = run.reduction
    return 100.0 * red.idle_share if red.n_device_events else None
