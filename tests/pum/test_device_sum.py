"""Sums reduced on the device: ``sum()`` of a pending value on a fused
device, where the value's opcode bounds its lanes, comes back as a few
uint32 partial sums of the lanes instead of the lanes. The total is
exact against NumPy on every evaluator; every other sum stays the host's
NumPy sum, and the cost plane never sees the difference."""

import numpy as np
import pytest

import repro.pum as pum
from repro.core import realworld
from repro.core.engine import LazySum
from repro.kernels import fused_program as fp

pytestmark = pytest.mark.fused

N = 1001  # elements: no multiple of 32, so every flush has padding lanes


def _values(seed, n=N, width=16):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 1 << width, n, dtype=np.uint64)
    b = rng.integers(0, 1 << width, n, dtype=np.uint64)
    a[::7] = (1 << width) - 1   # all ones: reduce_and is 1 somewhere
    a[::11] = 0                 # zeros: reduce_or is 0 somewhere
    return a, b


def _words(seed, n=N):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 1 << 64, n, dtype=np.uint64),
            rng.integers(0, 1 << 64, n, dtype=np.uint64))


# (query on a fused device, its per-lane NumPy reference): each summed
# value's opcode bounds its lanes (popcount, less, reduce_*).
_CASES = {
    "popcount": (lambda x, b: (x ^ b).popcount(),
                 lambda a, b: np.bitwise_count(a ^ b)),
    "less": (lambda x, b: x < b, lambda a, b: a < b),
    "reduce_and": (lambda x, b: x.reduce_bits("and"),
                   lambda a, b: a == 0xFFFF),
    "reduce_or": (lambda x, b: x.reduce_bits("or"), lambda a, b: a != 0),
    "reduce_xor": (lambda x, b: x.reduce_bits("xor"),
                   lambda a, b: np.bitwise_count(a) & 1),
}

# fused_backend, and the word pipeline's CPU path pinned: jitted (as on a
# chip) or the NumPy short-circuit.
_PATHS = [("words-cpu", 1), ("words-cpu", 1 << 40), ("ref-vertical", 1)]
_PATH_IDS = ["words-jitted", "words-numpy", "ref-vertical"]


@pytest.fixture(params=_PATHS, ids=_PATH_IDS)
def fused_device(request, monkeypatch):
    backend, cutoff = request.param
    monkeypatch.setattr(fp, "_NP_CUTOFF_WIRE_OPS", cutoff)
    dev = pum.device(width=16, fuse=True, fused_backend=backend)
    yield dev
    dev.close()


def _fetched(tr):
    return [a["bytes"] for name, *_, a in tr.events if name == "flush.fetch"]


def _partial_bytes(lanes, bound=32):
    """Bytes of the uint32 partials of ``lanes`` lanes, padded to 32."""
    blocks, columns = fp.sum_partials(-(-lanes // 32) * 32, bound)
    return blocks * columns * 4


@pytest.mark.parametrize("case", sorted(_CASES))
def test_device_sum_is_exact(fused_device, case):
    query, ref = _CASES[case]
    a, b = _values(len(case))
    want = ref(a, b).astype(np.uint64).sum()
    with pum.profile(fused_device) as tr:
        total = query(fused_device.asarray(a), b).sum()
        assert isinstance(total, LazySum)
        got = total.materialize()
    assert type(got) is np.uint64 and got == want
    c = fused_device.counters
    assert c["engine.sums.device"] == 1 and "engine.sums.host" not in c
    assert _fetched(tr) == [_partial_bytes(N)]  # 128 partials, no lanes


def test_device_sum_of_a_raw_bitmap_popcount(fused_device):
    """The bitmap-index shape: a raw AND of packed words and its popcount
    over the words' 64 bits, two 32-bit lanes a word, summed."""
    w1, w2 = _words(3)
    want = np.bitwise_count(w1 & w2).astype(np.uint64).sum()
    with pum.profile(fused_device) as tr:
        got = int((fused_device.asarray(w1) & w2).popcount(width=64).sum())
    assert got == want
    assert fused_device.counters["engine.sums.device"] == 1
    assert _fetched(tr) == [_partial_bytes(2 * N)] == [32 * 4]


def test_an_all_ones_bitmap_counts_every_bit(fused_device):
    ones = np.full(N, 2**64 - 1, np.uint64)
    total = (fused_device.asarray(ones) & ones).popcount(width=64).sum()
    assert int(total) == 64 * N


def test_the_handle_reads_as_the_host_sum():
    a, b = _values(9)
    dev = pum.device(width=16, fuse=True)
    want = (a < b).astype(np.uint64).sum()
    total = (dev.asarray(a) < b).sum()
    assert repr(total) == "LazySum(pending)"
    assert int(total) == want and total.__index__() == want
    assert float(total) == float(want)
    assert np.asarray(total).dtype == np.uint64
    assert np.asarray(total).shape == ()
    assert total == want and total != want + 1
    assert total < want + 1 and total >= want and not total > want
    assert total + 1 == want + 1 and 1 + total == want + 1
    assert total * 2 == 2 * want and total // 2 == want // 2
    assert total - 0 == want and want - total == 0
    assert hash(total) == hash(want)
    assert [0, 1, 2][total % 3] == want % 3  # usable as an index
    assert repr(total) == f"LazySum({int(want)})"


@pytest.mark.parametrize("lanes, bound, devices, want", [
    (1 << 25, 32, 1, (1, 128)),  # bitmap index, 2^30 users on one chip
    (1 << 27, 32, 4, (4, 128)),  # 2^32 users over four chips
    (1 << 34, 32, 1, (2, 128)),  # 2^27 lanes of 32 a partial reach 2^32
    ((1 << 34) - 128, 32, 1, (1, 128)),  # one row less stays below it
    (1 << 39, 1, 1, (2, 128)),   # 0/1 lanes: 2^32 of them would wrap
    (2016, 32, 1, (1, 32)),      # lanes a multiple of 32 only
    (2112, 32, 1, (1, 64)),
    (128, 32, 4, (4, 32)),
    (64, 1 << 31, 1, (1, 64)),   # lanes of 2^31: one lane a partial
])
def test_partial_length_rule(lanes, bound, devices, want):
    blocks, columns = fp.sum_partials(lanes, bound, devices)
    assert (blocks, columns) == want
    assert blocks % devices == 0 and lanes % (blocks * columns) == 0
    assert lanes // (blocks * columns) * bound < 1 << 32  # cannot wrap


def test_partial_length_rule_refuses_what_cannot_split():
    with pytest.raises(ValueError, match="cannot split"):
        fp.sum_partials(96, 1 << 31)  # 3 x 32 lanes: no power-of-two cut
    with pytest.raises(ValueError, match="cannot split"):
        fp.sum_partials(96, 1, devices=2)  # not 32 lanes a device


@pytest.mark.parametrize("lanes", [64, 50])
def test_partials_do_not_wrap_at_the_bound(lanes):
    """Lanes at the largest value the rule allows sum exactly: each
    partial stays below 2^32, and only the first ``lanes`` count."""
    wire = np.full(64, (1 << 31) - 1, np.uint32).view(np.int32)
    parts = fp.sum_lanes(wire, lanes, 1 << 31, xp=np)
    assert parts.dtype == np.uint32 and parts.size == 64  # one lane each
    assert int(parts.sum(dtype=np.uint64)) == lanes * ((1 << 31) - 1)


def test_lane_bound_of_each_opcode():
    bounds = {op: fp.lane_bound(op, 32) for op in fp.OPCODES}
    assert bounds.pop("popcount") == 32
    assert fp.lane_bound("popcount", 16) == 16
    for op in ("less", "reduce_and", "reduce_or", "reduce_xor"):
        assert bounds.pop(op) == 1
    assert set(bounds.values()) == {None}  # the rest span the width


def _popcount_sum_program(n_in=2):
    ops = (fp.FusedOp("and", (0, 1)), fp.FusedOp("popcount", (2,)),
           fp.FusedOp("less", (0, 1)))
    return fp.FusedProgram(width=32, n_inputs=n_in, ops=ops,
                           outputs=(2, 3, 4), reduced=(3, 4))


@pytest.mark.parametrize("backend", ["words-cpu", "ref-vertical",
                                     "pallas-tpu"])
def test_pipeline_masks_the_padding_lanes(backend):
    """Leaves whose padding lanes hold data: the reduced outputs count
    the first ``lanes`` lanes only, the unreduced output stays lanes.
    ``pallas-tpu`` runs in interpret mode here."""
    rng = np.random.default_rng(5)
    size, lanes = 1056, 1000     # 33 groups of 32; the Pallas block pads
    x, y = (rng.integers(0, 1 << 32, size, dtype=np.uint64)
            .astype(np.uint32).view(np.int32) for _ in range(2))
    program = _popcount_sum_program()
    pipeline = fp.get_pipeline(program, backend=backend,
                               interpret=backend == "pallas-tpu")
    anded, pc, lt = (np.asarray(o) for o in pipeline(x, y, lanes=lanes))
    ux, uy = x.view(np.uint32)[:lanes], y.view(np.uint32)[:lanes]
    np.testing.assert_array_equal(anded.view(np.uint32), (x & y).view(
        np.uint32))
    assert pc.dtype == lt.dtype == np.uint32 and pc.size == lt.size == 32
    assert int(pc.sum()) == int(np.bitwise_count(ux & uy).sum())
    assert int(lt.sum()) == int((ux < uy).sum())


def test_reduced_outputs_key_their_own_pipeline():
    program = _popcount_sum_program()
    plain = fp.FusedProgram(program.width, program.n_inputs, program.ops,
                            program.outputs)
    assert plain.reduced == () and plain != program
    assert fp.get_pipeline(plain) is not fp.get_pipeline(program)


def test_optimizer_keeps_lanes_where_any_request_wants_them():
    """CSE maps two requests onto one popcount: one reduced, one wanting
    the lanes. The lanes win; a sum-only output stays reduced."""
    ops = (fp.FusedOp("popcount", (0,)), fp.FusedOp("popcount", (0,)),
           fp.FusedOp("less", (0, 1)))
    program = fp.FusedProgram(width=32, n_inputs=2, ops=ops,
                              outputs=(2, 3, 4), reduced=(2, 4))
    opt, out_pos, _ = fp.optimize_program(program)
    assert out_pos == (0, 0, 1)
    assert opt.reduced == (opt.outputs[1],)


def test_a_program_may_reduce_only_what_an_opcode_bounds():
    program = fp.FusedProgram(width=32, n_inputs=2,
                              ops=(fp.FusedOp("add", (0, 1)),),
                              outputs=(2,), reduced=(2,))
    with pytest.raises(ValueError, match="no opcode bounds"):
        fp.get_pipeline(program, backend="words-cpu")


# --------------------------------------------------------------------- #
# Where the sum stays on the host, bit-exact as before
# --------------------------------------------------------------------- #


def _host_sum_cases():
    a, b = _values(21)
    less = (a < b).astype(np.uint64)
    return {
        "axis": (16, 32, lambda x: (x < b).sum(axis=0), less.sum(axis=0)),
        "dtype": (16, 32, lambda x: (x < b).sum(dtype=np.uint64),
                  less.sum(dtype=np.uint64)),
        "layout64": (16, 64, lambda x: (x < b).sum(), less.sum()),
        "unbounded-opcode": (16, 32, lambda x: (x + b).sum(),
                             ((a + b) & np.uint64(0xFFFF)).sum()),
    }, a


@pytest.mark.parametrize("case", ["axis", "dtype", "layout64",
                                  "unbounded-opcode"])
def test_host_sum_fallbacks(case):
    cases, a = _host_sum_cases()
    width, layout, query, want = cases[case]
    dev = pum.device(width=width, fuse=True, layout=layout)
    with pum.profile(dev):
        got = query(dev.asarray(a))
    assert not isinstance(got, LazySum)
    assert type(got) is type(want) and got == want
    assert dev.counters["engine.sums.host"] == 1
    assert "engine.sums.device" not in dev.counters


def test_eager_and_materialized_values_sum_on_the_host():
    a, b = _values(22)
    want = (a < b).astype(np.uint64).sum()
    eager = pum.device(width=16, fuse=False)
    got = (eager.asarray(a) < b).sum()
    assert type(got) is np.uint64 and got == want
    dev = pum.device(width=16, fuse=True)
    lt = dev.asarray(a) < b
    lt.to_numpy()
    got = lt.sum()
    assert type(got) is np.uint64 and got == want


def test_held_lanes_sum_on_the_host():
    """The summed value's own handle is live: its lanes cross anyway, so
    the flush sums them on the host and keeps one output."""
    w1, w2 = _words(4)
    dev = pum.device(width=32, fuse=True)
    with pum.profile(dev) as tr:
        pc = (dev.asarray(w1) & w2).popcount(width=64)
        total = pc.sum()
        assert isinstance(total, LazySum)
        lanes = pc.to_numpy()
        np.testing.assert_array_equal(lanes, np.bitwise_count(w1 & w2))
        lanes[:] = 0  # the caller's own buffer: the total was taken
    assert total == np.bitwise_count(w1 & w2).astype(np.uint64).sum()
    assert dev.counters["engine.sums.host"] == 1
    assert "engine.sums.device" not in dev.counters
    assert _fetched(tr) == [-(-2 * N // 32) * 32 * 4]  # padded lanes


def _injecting_device():
    dev = pum.device(width=16, fuse=True, banks=4)
    dev.calibrate(inject=True, n_subarrays=4, n_columns=64, n_patterns=4)
    return dev


def test_fault_injection_sums_after_the_vote():
    a, b = _values(23)
    want = (a < b).astype(np.uint64).sum()
    dev = _injecting_device()
    with pum.profile(dev):
        got = (dev.asarray(a) < b).sum()
    assert type(got) is np.uint64 and got == want
    assert dev.counters["engine.sums.host"] == 1


def test_injection_turned_on_before_the_flush_sums_on_the_host():
    a, b = _values(24)
    want = (a < b).astype(np.uint64).sum()
    dev = pum.device(width=16, fuse=True, banks=4)
    total = (dev.asarray(a) < b).sum()
    assert isinstance(total, LazySum)
    dev.calibrate(inject=True, n_subarrays=4, n_columns=64, n_patterns=4)
    with pum.profile(dev):
        assert total == want
    assert dev.counters["engine.sums.host"] == 1
    assert "engine.sums.device" not in dev.counters


def test_engine_stats_do_not_see_where_the_sum_ran():
    """The bitmap-index query prices the same on an eager device, a
    fused device summing on the device, and one summing on the host."""
    rng = np.random.default_rng(8)
    days = rng.integers(0, 1 << 64, (5, 700), dtype=np.uint64)
    stats, counts = [], []
    for fuse in (False, True):
        dev = pum.device(width=32, fuse=fuse)
        got, _, _ = realworld.bmi_active_users(dev, days)
        counts.append(got)
        stats.append(dev.stats)
    dev = pum.device(width=32, fuse=True)
    acc = dev.asarray(days[0])
    for d in days[1:]:
        acc = acc & d
    counts.append(int(acc.popcount(width=64).to_numpy().sum()))
    stats.append(dev.stats)
    assert counts[0] == counts[1] == counts[2]
    assert stats[0] == stats[1] == stats[2]


def test_capture_refuses_a_sum():
    dev = pum.device(width=16, fuse=True)
    prog = dev.capture(lambda x: (x < 3).sum())
    with pytest.raises(ValueError):
        prog(np.arange(64, dtype=np.uint64))
