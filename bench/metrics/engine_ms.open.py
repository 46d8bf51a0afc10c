"""Engine: median per batch of the batch's engine call, from
``execute_started_at`` to ``completed_at``, in ms."""
import numpy as np


def read(run):
    per_batch = {}
    for s in run.done():
        t = s.ticket
        per_batch[t.execute_started_at] = t.completed_at - t.execute_started_at
    return float(np.median(list(per_batch.values()))) * 1e3 if per_batch \
        else None
