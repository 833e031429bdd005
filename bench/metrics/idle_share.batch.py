"""Share of the traced window in which no operation ran on the device
(trace: 1 - union of op intervals / window), in percent."""


def read(run):
    return 100.0 * run.trace.idle_share if run.trace else None
