"""Share of the traced window in which no operation ran on the device,
in percent, averaged over the cell's chips: 100 * (1 - busy / window),
busy being the union of the device-op intervals (``TraceRun.busy``)."""


def read(run):
    if not run.ops:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
