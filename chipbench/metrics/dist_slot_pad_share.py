"""Share of the dist exchange's slots that hold no key, in percent: the
loop's ``slot_pad_shares``, ``1 - n / (shards² · dist_capacity)`` per call,
averaged over the window's calls.  A program that reports no
``dist_capacity`` gives nothing to read."""


def read(run):
    shares = run.counters.get("slot_pad_shares") or []
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
