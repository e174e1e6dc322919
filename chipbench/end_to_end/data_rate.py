"""User-data gigabytes covered by the queries answered in the window,
over the window's seconds (from the first query sent to the last one
answered)."""


def read(w):
    if not w.n_queries or w.seconds <= 0:
        return None
    return sum(q.nbytes for q in w.answered) / 1e9 / w.seconds
