"""Share of the device's busy time, in percent, spent in collective ops
(all-to-all, all-gather, all-reduce and kin) over the window, averaged
over the cell's chips."""

from chipbench.trace import is_collective


def read(run):
    if run.trace is None or run.chips < 2:
        return None
    shares = []
    for d in range(run.chips):
        busy = run.trace.busy_s(d)
        if busy > 0:
            shares.append(run.trace.op_time_s(d, is_collective) / busy)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
