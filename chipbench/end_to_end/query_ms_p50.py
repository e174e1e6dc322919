"""Median latency, in milliseconds, of every query answered in the
window, taken on the client's side (open loop: from the time it was due)."""

import statistics


def read(w):
    lat = [q.latency_s for q in w.answered]
    return 1e3 * statistics.median(lat) if lat else None
