"""Entity search: share of the bandwidth roofline reached by the search
programs, in %.

Each run of a search program (HLO modules ``_entity_match``,
``_entity_match_segmented``, ``_entity_match_delta``) scans the whole text
bank once; no exact implementation over these stores can read less than
every valid row at the width of the smallest copy the store keeps
(``bench/flops.py``). The least time of a run is those bytes over the
chip's HBM bandwidth; the share is the least time of all runs over their
summed device time. Found by module name, the count is the same whichever
scan (fp32, int8, int4) runs inside.
"""
from bench import trace as tr

NAMES = ("_entity_match",)


def read(run):
    if run.trace is None:
        return None
    n, secs = 0, 0.0
    for evs in run.trace.modules.values():
        c, s = tr.group_seconds(evs, lambda name: any(k in name
                                                      for k in NAMES))
        n, secs = n + c, secs + s
    if not n or secs <= 0:
        return None
    least = n * run.search_least_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs
