"""Front door: median wait from a query's due time to its admission into a
batch (``RuntimeTicket.admitted_at``), in ms, over the answered queries."""
import numpy as np


def read(run):
    w = [(s.ticket.admitted_at - s.due) * 1e3 for s in run.done()
         if s.ticket.admitted_at is not None]
    return float(np.median(w)) if w else None
