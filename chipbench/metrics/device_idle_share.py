"""Share of the traced window, in percent, in which no op ran on the
device: one minus the union of op intervals over the window, averaged over
the cell's chips."""


def read(run):
    if run.trace is None:
        return None
    chips = range(run.chips)
    return 100.0 * sum(run.trace.idle_share(d) for d in chips) / run.chips
