"""Column range-scan deployment (``bitweaving-2p26.json``): data from the
seed, the timed query through ``repro.pum``, and the plain NumPy
reference.

The reference and the control use NumPy alone: nothing of the program
under test, and nothing it made.
"""

import numpy as np


def check(cfg: dict) -> None:
    if not 1 <= cfg["value_bits"] <= 32 or cfg["rows"] < 1:
        raise ValueError("value_bits must be 1..32 and rows positive")


def make_data(cfg: dict, rng: np.random.Generator) -> dict:
    """``column``: [rows] uint64 values of ``value_bits`` bits."""
    return {"column": rng.integers(0, 1 << cfg["value_bits"], cfg["rows"],
                                   dtype=np.uint64)}


def run_query(dev, data: dict, params: dict, mark) -> int:
    """The user's query with ``PumArray`` operators and Python-int
    bounds: record the predicate (``build``), then materialize it and sum
    on the host (``materialize``)."""
    c1, c2 = params["bounds"]
    with mark("build"):
        col = dev.asarray(data["column"])
        hit = (col >= c1) & (col <= c2)
    with mark("materialize"):
        return int(hit.sum())


def query_bytes(cfg: dict, params: dict) -> int:
    """User-data bytes a query covers: ``value_bits`` per row."""
    return cfg["rows"] * cfg["value_bits"] // 8


def _histogram(cfg: dict, column: np.ndarray) -> np.ndarray:
    """``cum[v]``: rows with a value below ``v``."""
    hist = np.bincount(column.view(np.int64), minlength=1 << cfg["value_bits"])
    return np.concatenate([[0], np.cumsum(hist, dtype=np.int64)])


def reference(cfg: dict, data: dict, queries: list) -> list:
    """Rows with ``c1 <= v <= c2``, counted exactly, per query."""
    cum = _histogram(cfg, data["column"])
    return [int(cum[c2 + 1] - cum[c1]) for c1, c2 in
            (q["bounds"] for q in queries)]


def control(cfg: dict, data: dict, queries: list) -> list:
    """The reference with the exact-count guarantee broken: it reads
    every eighth row and scales the count by 8 (an estimate from a 1/8
    sample of the rows)."""
    cum = _histogram(cfg, data["column"][::8].copy())
    return [8 * int(cum[c2 + 1] - cum[c1]) for c1, c2 in
            (q["bounds"] for q in queries)]
