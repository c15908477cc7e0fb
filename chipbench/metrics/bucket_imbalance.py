"""The fullest bucket over the mean bucket, as a factor: the
configuration's ``processors`` P times the loop's ``max_bucket_shares``
(``max(counts) / n`` a call), averaged over the window's calls.  1 is
perfect balance; P is every key in one bucket.  A window whose calls
reported no ``counts`` gives nothing to read."""


def read(run):
    shares = run.counters.get("max_bucket_shares") or []
    if not shares:
        return None
    return run.config["processors"] * sum(shares) / len(shares)
