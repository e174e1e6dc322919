"""Bitmap-index deployment (``bmi-appb-2p30.json``): data from the seed,
the timed query through ``repro.pum``, and the plain NumPy reference.

The reference and the control use NumPy alone: nothing of the program
under test, and nothing it made.
"""

import numpy as np


def check(cfg: dict) -> None:
    if cfg["users"] % 64 or cfg["shard_users"] % 64:
        raise ValueError("users and shard_users must be multiples of 64")
    if cfg["tenants"] * cfg["shard_users"] != cfg["users"]:
        raise ValueError("tenants * shard_users must equal users")


def make_data(cfg: dict, rng: np.random.Generator) -> dict:
    """``days``: [days, users/64] packed uint64 activity bitmaps."""
    words = cfg["users"] // 64
    loyal = (rng.integers(0, 1 << 64, words, dtype=np.uint64)
             & rng.integers(0, 1 << 64, words, dtype=np.uint64)
             & rng.integers(0, 1 << 64, words, dtype=np.uint64))
    days = rng.integers(0, 1 << 64, (cfg["days"], words), dtype=np.uint64)
    days |= loyal
    return {"days": days, "shard_words": cfg["shard_users"] // 64}


def _tenant_slice(data: dict, params: dict) -> np.ndarray:
    days = data["days"]
    t = params.get("tenant")
    if t is None:
        return days
    w = data["shard_words"]
    return days[:, t * w:(t + 1) * w]


def run_query(dev, data: dict, params: dict, mark) -> int:
    """The user's query through the public application kernel: the whole
    table, or one tenant's shard when ``params`` names a tenant."""
    from repro.core import realworld
    got, _, _ = realworld.bmi_active_users(dev, _tenant_slice(data, params),
                                           verify=False)
    return got


def query_bytes(cfg: dict, params: dict) -> int:
    """User-data bytes a query covers: one bit per user and day."""
    users = cfg["users"] if params.get("tenant") is None \
        else cfg["shard_users"]
    return cfg["days"] * users // 8


def _counts(cfg: dict, every_word_count: np.ndarray, queries: list,
            scale: int = 1) -> list:
    whole = int(every_word_count.sum(dtype=np.int64)) * scale
    per_tenant = None
    if any(q.get("tenant") is not None for q in queries):
        per_tenant = every_word_count.reshape(cfg["tenants"], -1) \
            .sum(axis=1, dtype=np.int64) * scale
    return [whole if q.get("tenant") is None else int(per_tenant[q["tenant"]])
            for q in queries]


def reference(cfg: dict, data: dict, queries: list) -> list:
    """Users set in all days' bitmaps, counted exactly, per query."""
    every = np.bitwise_and.reduce(data["days"], axis=0)
    return _counts(cfg, np.bitwise_count(every), queries)


def control(cfg: dict, data: dict, queries: list) -> list:
    """The reference with the exact-count guarantee broken: it reads every
    eighth word of each bitmap and scales the count by 8 (an estimate
    from a 1/8 sample of the users)."""
    every = np.bitwise_and.reduce(data["days"], axis=0)
    sampled = np.zeros_like(every)
    sampled[::8] = every[::8]
    return _counts(cfg, np.bitwise_count(sampled), queries, scale=8)
