"""Share of the traced window in which no operation ran on the device
(``devtrace.idle_share``)."""

import devtrace


def read(run):
    return devtrace.idle_share(run.device)
