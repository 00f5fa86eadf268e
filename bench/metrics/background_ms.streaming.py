"""Host time of the background steps (seal, index build, slice build,
hand-off) in the window, per answered search (ms): the program's
``background_us_total`` counters, taken at the window's edges by the
streaming loop.  A program without them reads nothing."""


def read(run):
    steps = getattr(run, "background", None)
    if steps is None or not run.answered:
        return None
    return sum(v["us"] for v in steps.values()) / 1e3 / len(run.answered)
