"""round_roofline_share: one control window's share of the chip's memory
roofline.  The window is memory-bound (elementwise work, no matrix units),
so its least time is the byte floor (``lib/floor.py``: rates read once,
carry read and written once, outputs written once) over the chip's HBM
bandwidth; the time it took is the device's busy time in the traced window
over the windows completed there."""


def read(run):
    red = run.reduction
    if not red.n_device_events or run.windows == 0 or red.busy_s <= 0:
        return None
    least_s = run.floor_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (red.busy_s / run.windows)
