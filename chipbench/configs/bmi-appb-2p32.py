"""Bitmap-index deployment sharded over four chips (``bmi-appb-2p32.json``):
data from the seed, the timed query through ``repro.pum``, and the plain
NumPy reference.

The table (16.1 GB at 2^32 users) is made and checked in word ranges on
a pool of threads, one generator per day, so that set-up and the check
stay short. The reference and the control use NumPy alone: nothing of
the program under test, and nothing it made.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_WORDS = 1 << 22  # 32 MiB of a day's bitmap per generator draw


def check(cfg: dict) -> None:
    if cfg["users"] % 64 or cfg["shard_users"] % 64:
        raise ValueError("users and shard_users must be multiples of 64")
    if cfg["tenants"] * cfg["shard_users"] != cfg["users"]:
        raise ValueError("tenants * shard_users must equal users")


def _pool() -> ThreadPoolExecutor:
    return ThreadPoolExecutor(min(32, os.cpu_count() or 1))


def _fill(row: np.ndarray, seed: np.random.SeedSequence) -> None:
    """``row`` := uniform 64-bit words from its own generator."""
    bits = np.random.default_rng(seed).bit_generator
    for lo in range(0, row.size, BLOCK_WORDS):
        hi = min(row.size, lo + BLOCK_WORDS)
        row[lo:hi] = bits.random_raw(hi - lo)


def make_data(cfg: dict, rng: np.random.Generator) -> dict:
    """``days``: [days, users/64] packed uint64 activity bitmaps. A user
    is loyal (active every day) with probability 1/8; every other user
    is active on a day with probability 1/2."""
    words, n = cfg["users"] // 64, cfg["days"]
    seeds = np.random.SeedSequence(int(rng.integers(1 << 63))).spawn(n + 3)
    loyal = np.empty((3, words), np.uint64)
    days = np.empty((n, words), np.uint64)
    with _pool() as pool:
        list(pool.map(_fill, loyal, seeds[n:]))
        np.bitwise_and(loyal[0], loyal[1], out=loyal[0])
        np.bitwise_and(loyal[0], loyal[2], out=loyal[0])

        def day(d):
            _fill(days[d], seeds[d])
            np.bitwise_or(days[d], loyal[0], out=days[d])

        list(pool.map(day, range(n)))
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


def _word_counts(days: np.ndarray, stride: int = 1) -> np.ndarray:
    """Users set in every day's bitmap, counted per word, in word ranges
    on the pool; with ``stride`` k only every k-th word is read (the
    others count 0)."""
    out = np.zeros(days.shape[1], np.uint8)
    step = BLOCK_WORDS - BLOCK_WORDS % stride

    def block(lo):
        hi = min(days.shape[1], lo + step)
        every = np.bitwise_and.reduce(days[:, lo:hi:stride], axis=0)
        out[lo:hi:stride] = np.bitwise_count(every)

    with _pool() as pool:
        list(pool.map(block, range(0, days.shape[1], step)))
    return out


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
    return _counts(cfg, _word_counts(data["days"]), queries)


def control(cfg: dict, data: dict, queries: list) -> list:
    """The reference with the exact-count guarantee broken: it reads every
    eighth word of each bitmap and scales the count by 8 (an estimate
    from a 1/8 sample of the users)."""
    return _counts(cfg, _word_counts(data["days"], stride=8), queries,
                   scale=8)
