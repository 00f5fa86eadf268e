"""Programs JAX compiled, or loaded from its persistent cache, while the
window ran: a new shape reached the device after warm-up."""


def read(run):
    return run.compiles_in_window
