"""Share of the engine's bucket slots that hold no key, in percent:
``1 - n / (P * capacity_used)`` per call, averaged over the window's calls.
Calls that report no capacity (the mesh path) give nothing to read."""


def read(run):
    shares = run.counters.get("pad_shares") or []
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
