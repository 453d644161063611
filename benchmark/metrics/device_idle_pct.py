"""Share of the traced window in which no op ran on the device, in %.

The trace covers the first seconds of the measured window, gaps between
jobs and chunks included; busy time is the union of the device's op
intervals, averaged over the chips.
"""


def read(run):
    if run.trace is None or run.trace.busy_s() is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
