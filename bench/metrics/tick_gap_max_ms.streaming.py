"""The longest host-clock gap between two consecutive time-ticks on any
DML channel in the window (ms), as the streaming loop took it from the log
broker.  A program that keeps no such record reads nothing."""


def read(run):
    return getattr(run, "tick_gap_max_ms", None)
