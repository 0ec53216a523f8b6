"""device_idle_share (%): the share of the traced window in which a device
runs no operation, 1 - busy / window, averaged over the cell's devices.
Busy is the union of the device's op intervals (``bench/trace.py``)."""


def read(run):
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
