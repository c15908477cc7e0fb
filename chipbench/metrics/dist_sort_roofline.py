"""Share of the HBM roofline, in percent, of the dist sort executable over
the cell's chips: each call's valid keys read once and written once at the
peak bandwidth of all the cell's chips together, over the first chip's
device time of the ``jit_dist_sort`` runs that start inside the
benchmark's spans of the calls that lie whole in the window.

The peak is summed over the chips because the keys are spread over them:
each chip reads and writes its own shard in its own HBM, so the least time
of the whole sort is its bytes over ``chips`` times one chip's bandwidth.
The first chip's time stands for every chip's, since the exchange's
all_to_all holds each chip until the others reach it.  The exchange is
bounded by the chips' interconnect, which has no peak in ``peaks.json``,
so this share counts HBM traffic only.
"""

from chipbench import roofline
from chipbench.drivers import SPAN_CALL

MODULE = "jit_dist_sort"


def read(run):
    if run.trace is None or run.chips < 2:
        return None
    tr = run.trace
    calls = [(s, e) for s, e in tr.spans(SPAN_CALL) if tr.t0 <= s and e <= tr.t1]
    runs = [(s, e) for s, e, name in tr.module_runs(0, inside=calls)
            if name.split("(", 1)[0] == MODULE]
    if not calls or not runs:
        return None
    seconds = sum(e - s for s, e in runs) * 1e-9
    n_bytes = len(calls) * roofline.sort_bytes(run.config["n"], run.counters["itemsize"])
    all_chips = {"hbm_bytes_per_s": run.chips * run.peaks["hbm_bytes_per_s"]}
    return roofline.hbm_roofline_pct(n_bytes, seconds, all_chips)
