"""Front door: queries answered per batch the runtime executed in the
window (coalescing)."""


def read(run):
    return len(run.done()) / run.batches if run.batches else None
