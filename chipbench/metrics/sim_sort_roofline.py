"""Share of the HBM roofline, in percent, of the device work of
``SortEngine.sort`` on one chip: each call's valid keys read once and
written once at peak bandwidth, over the device time of the executables
that ran inside the benchmark's spans of the calls that lie whole in the
window.  Host-device copies are not device ops, so that time leaves them
out."""

from chipbench import roofline
from chipbench.drivers import SPAN_CALL


def read(run):
    if run.trace is None or run.chips != 1:
        return None
    tr = run.trace
    calls = [(s, e) for s, e in tr.spans(SPAN_CALL) if tr.t0 <= s and e <= tr.t1]
    runs = tr.module_runs(0, inside=calls)
    if not calls or not runs:
        return None
    seconds = sum(e - s for s, e, _ in runs) * 1e-9
    n_bytes = len(calls) * roofline.sort_bytes(run.config["n"], run.counters["itemsize"])
    return roofline.hbm_roofline_pct(n_bytes, seconds, run.peaks)
