"""Bank-parallel MemoryController: bank machines, multiplexer, refresher."""

import pytest

from repro.controller import (BankMachine, BankState, MemoryController,
                              retarget_program)
from repro.core import commands as cmds
from repro.core.commands import Cmd, Op
from repro.core.cost_model import CostModel
from repro.core.timing import DDR4_2400 as T

ALL_PROGRAMS = {
    "apa": lambda b: cmds.prog_apa_charge_share(b, 0, 1, T),
    "aap": lambda b: cmds.prog_aap_multi_row_init(b, 0, 1, T),
    "bulk_write": lambda b: cmds.prog_bulk_write(b, 0, 1, 8, T),
    "write_row": lambda b: cmds.prog_write_row(b, 5, 8, T),
    "read_row": lambda b: cmds.prog_read_row(b, 5, 8, T),
    "frac": lambda b: cmds.prog_frac(b, 3, T),
}


# --------------------------------------------------------------------- #
# BankMachine: open-row tracking + precharge policy
# --------------------------------------------------------------------- #

def test_bank_machine_row_hit_miss_transitions():
    bm = BankMachine(0, T)
    bm.enqueue_access(5)                  # idle -> ACT + RD
    bm.enqueue_access(5)                  # hit  -> RD only
    bm.enqueue_access(9)                  # miss -> PRE + ACT + RD
    ops = [q.cmd.op for q in bm.queue]
    assert ops == [Op.ACT, Op.RD, Op.RD, Op.PRE, Op.ACT, Op.RD]
    # FSM state follows issued commands.
    assert bm.state is BankState.IDLE
    t = 0.0
    for _ in range(2):
        t = max(t + 1, bm.earliest_issue())
        bm.issue(t)
    assert bm.state is BankState.ACTIVE and bm.open_row == 5
    for _ in range(2):                    # hit RD + the PRE
        t = max(t + 1, bm.earliest_issue())
        bm.issue(t)
    assert bm.state is BankState.IDLE and bm.open_row is None


def test_bank_machine_closed_page_auto_precharges():
    bm = BankMachine(0, T, open_page=False)
    bm.enqueue_access(5)
    ops = [q.cmd.op for q in bm.queue]
    assert ops == [Op.ACT, Op.RD, Op.PRE]
    bm.enqueue_access(5)                  # closed page: never a hit
    assert [q.cmd.op for q in bm.queue][3:] == [Op.ACT, Op.RD, Op.PRE]


def test_bank_machine_sequence_boundaries():
    bm = BankMachine(2, T)
    bm.enqueue_program(cmds.prog_apa_charge_share(2, 0, 1, T))
    bm.enqueue_program(cmds.prog_frac(2, 3, T))
    starts = [q.seq_start for q in bm.queue]
    assert starts == [True, False, False, False, False, True, False, False]
    assert len({q.seq_id for q in bm.queue}) == 2


# --------------------------------------------------------------------- #
# Equivalence: single-bank controller == sequential CommandScheduler
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("name", sorted(ALL_PROGRAMS))
def test_single_bank_matches_legacy_scheduler(name):
    prog = ALL_PROGRAMS[name](0)
    legacy = cmds.CommandScheduler(T).schedule(prog)
    ctrl = MemoryController(n_banks=16).schedule(prog)
    assert ctrl.total_ns == pytest.approx(legacy.total_ns, abs=1.0)
    for (c, t_ctrl), t_leg in zip(ctrl.events, legacy.issue_times):
        assert t_ctrl == pytest.approx(t_leg, abs=1.0)
    assert ctrl.n_acts == legacy.n_acts
    assert ctrl.energy_j == pytest.approx(legacy.energy_j)


def test_maj_unit_programs_match_closed_form_cost():
    cm = CostModel()
    for m, n_rg in [(3, 4), (3, 8), (5, 8), (5, 16)]:
        unit = cm.maj_unit_programs(m, n_rg)
        sched = MemoryController(n_banks=1).schedule_batch(
            unit, 1, refresh=False).total_ns
        assert sched == pytest.approx(cm.maj_op(m, n_rg).latency_ns,
                                      abs=1e-6)


def test_schedule_result_events_are_auditable():
    prog = cmds.prog_bulk_write(0, 0, 1, 4, T)
    res = cmds.CommandScheduler(T).schedule(prog)
    assert len(res.cmds) == len(res.issue_times) == len(prog)
    assert [c.tag for c, _ in res.events] == [c.tag for c in prog]
    # Controller traces interleave banks; events keep the (cmd, t) pairing.
    multi = [retarget_program(prog, b) for b in range(4)]
    tr = MemoryController(n_banks=4).schedule(multi)
    assert len(tr.events) == 4 * len(prog)
    times = [t for _, t in tr.events]
    assert times == sorted(times)
    by_bank = {b: [t for c, t in tr.events if c.bank == b] for b in range(4)}
    assert all(len(v) == len(prog) for v in by_bank.values())


# --------------------------------------------------------------------- #
# Multiplexer: rank-wide tRRD / tFAW under concurrent programs
# --------------------------------------------------------------------- #

def test_multiplexer_enforces_trrd_and_tfaw():
    progs = [[Cmd(Op.ACT, b, 0, 0.0, f"act{b}")] for b in range(8)]
    tr = MemoryController(n_banks=8).schedule(progs)
    acts = sorted(t for c, t in tr.events if c.op is Op.ACT)
    assert len(acts) == 8
    for a, b in zip(acts, acts[1:]):
        assert b - a >= T.trrd_s - 1e-9
    for i in range(len(acts) - 4):
        assert acts[i + 4] - acts[i] >= T.tfaw - 1e-9


def test_multiplexer_overlaps_banks_but_not_fully():
    """Concurrent APA programs overlap (makespan < sequential) yet stay
    tFAW/tRRD-limited (makespan > one program)."""
    single = cmds.CommandScheduler(T).schedule(ALL_PROGRAMS["apa"](0))
    n = 8
    progs = [ALL_PROGRAMS["apa"](b) for b in range(n)]
    flat = [c for p in progs for c in p]
    seq = cmds.CommandScheduler(T).schedule(flat)
    par = MemoryController(n_banks=n).schedule(progs)
    assert par.total_ns < seq.total_ns          # strict overlap win
    assert par.total_ns > single.total_ns       # but not a free 8x


@pytest.mark.parametrize("banks", [2, 4, 8, 16])
def test_multibank_throughput_beats_sequential(banks):
    cm = CostModel()
    unit = cm.maj_unit_programs(3, 8)
    n_ops = 2 * banks
    progs = [retarget_program(p, i % banks)
             for i in range(n_ops) for p in unit]
    flat = [c for p in progs for c in p]
    seq_ns = cmds.CommandScheduler(T).schedule(flat).total_ns
    ctrl_ns = MemoryController(n_banks=banks).schedule(progs).total_ns
    assert ctrl_ns < seq_ns  # scheduled multi-bank MAJ strictly faster


# --------------------------------------------------------------------- #
# Refresher: preemption of in-flight PuM sequences
# --------------------------------------------------------------------- #

def test_refresher_preempts_apa_stream_atomically():
    ctrl = MemoryController(n_banks=1, trefi=300.0, trfc=100.0)
    stream = [cmds.prog_apa_charge_share(0, 0, 1, T) for _ in range(10)]
    tr = ctrl.schedule(stream)
    assert tr.n_refreshes > 0
    assert tr.refresh_stall_ns > 0
    # No command issues strictly inside a refresh lockout window (the
    # drained sequence's trailing NOP marker may coincide with its start).
    for start, end in tr.refresh_windows:
        for _, t in tr.events:
            assert not (start + 1e-9 < t < end - 1e-9)
    # An APA sequence is never split by REF: each program's 5 commands lie
    # entirely on one side of every lockout window.
    per_prog = [tr.issue_times[i:i + 5]
                for i in range(0, len(tr.issue_times), 5)]
    for times in per_prog:
        for start, end in tr.refresh_windows:
            assert all(t <= start + 1e-9 for t in times) or \
                all(t >= end - 1e-9 for t in times)
    # Refresh interference is a real latency term.
    no_ref = ctrl.schedule(stream, refresh=False)
    assert tr.total_ns > no_ref.total_ns


def test_refresh_stall_scales_with_trefi():
    cm = CostModel()
    unit = cm.maj_unit_programs(3, 8)
    slow = MemoryController(n_banks=16).batch_cost(unit, 16)
    fast = MemoryController(n_banks=16, trefi=3900.0).batch_cost(unit, 16)
    assert 1.0 < slow.refresh_factor < fast.refresh_factor


def test_refresh_reopens_row_for_pending_access():
    """REF closes every row; a queued row-hit RD gets a re-ACT injected."""
    ctrl = MemoryController(n_banks=1, trefi=120.0, trfc=60.0)
    progs = [[Cmd(Op.ACT, 0, 7, 0.0, "a"), Cmd(Op.RD, 0, 7, T.trcd, "r")]]
    progs += [[Cmd(Op.RD, 0, 7, T.tccd_l, f"hit{i}")] for i in range(40)]
    tr = ctrl.schedule(progs)
    assert tr.n_refreshes >= 1
    for _, end in tr.refresh_windows:
        after = [c for c, t in tr.events if t >= end - 1e-9]
        if after:  # the first command after a lockout re-opens the row
            assert after[0].op is Op.ACT and after[0].tag == "bm.reopen"


# --------------------------------------------------------------------- #
# Batch cost + engine integration
# --------------------------------------------------------------------- #

def test_batch_cost_speedup_bounded_and_cached():
    ctrl = MemoryController(n_banks=16)
    unit = CostModel().maj_unit_programs(3, 8)
    bc = ctrl.batch_cost(unit, 16)
    assert 1.0 < bc.parallel_speedup <= 16.0
    assert bc.refresh_factor >= 1.0
    assert ctrl.batch_cost(unit, 16) is bc  # cached


def test_engine_controller_pricing_adds_refresh_term():
    import numpy as np
    import repro.pum as pum
    legacy = pum.device(mfr="M", width=32, banks=16, fuse=False)
    ctrl = pum.device(mfr="M", width=32, banks=16, controller="auto",
                      fuse=False)
    a = np.arange(65536 * 4, dtype=np.uint64)
    legacy.asarray(a) + a
    ctrl.asarray(a) + a
    assert legacy.stats.refresh_stall_ns == 0.0
    assert ctrl.stats.refresh_stall_ns > 0.0
    # Scheduled pricing can only be slower than the ideal closed-form divide.
    assert ctrl.stats.latency_ns >= legacy.stats.latency_ns
    # Dataplane results are unaffected by the cost plane.
    np.testing.assert_array_equal(np.asarray(legacy.asarray(a) + a),
                                  np.asarray(ctrl.asarray(a) + a))
