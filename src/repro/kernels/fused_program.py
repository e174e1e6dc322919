"""Fused bit-plane program compiler: one trace for a whole op graph.

PULSAR's performance case is command-stream economy — many-input MAJ and
Multi-RowInit collapse chains of per-op activations into one fused sequence
(§5.2). This module is the dataplane mirror of that argument: instead of the
engine dispatching every op through Python with its own layout conversion
and intermediate materialization, a recorded op sequence (``FusedProgram``)
compiles into a *single* ``jax.jit`` trace that

  1. transposes each operand horizontal -> vertical ONCE (bit_transpose32),
  2. evaluates the whole program on bit-planes (intermediates stay in
     registers/fusion scope — XLA sees one elementwise DAG),
  3. transposes the requested outputs back ONCE.

The same program IR runs in three backends, all bit-exact against each
other (tests/kernels):

  * ``run_program_pallas`` — Pallas kernel sharing the ``BLOCK_WORDS``
    (8, 128) tiling of maj_n / bitserial_add: the full program executes per
    VMEM-resident block, so N ops cost one HBM round-trip instead of N.
  * ``run_program_ref`` — the vertical jnp oracle (semantics anchor,
    validates the Pallas kernel in interpret mode).
  * ``run_program_words`` — horizontal word-domain evaluator: the CPU
    execution path. On a scalar ISA the vertical form loses ~10x (a ripple
    add is 32 dependent plane passes vs one hardware add), and the two
    bit_transpose32 calls bracketing the program cancel algebraically —
    so the CPU pipeline elides the layout conversion entirely and fuses
    the whole graph in the word domain (same elimination of per-op
    dispatch/materialization, minus the transposes). This is the same
    CPU-vs-TPU dispatch split ops.py applies to every kernel.
  * ``run_program_pairs`` — the jitted 64-bit lane path: a 64-bit lane
    evaluates as a (lo, hi) pair of uint32 words (the wire layout's two
    int32 words, bitcast), with the carry chained across the pair in
    every arithmetic op — 64-bit add/sub/mul/divmod never materialize a
    uint64 dtype, so the wide path runs under ``jax.jit`` without the
    global x64 flag. divmod is Knuth Algorithm D over base-2^16 digits
    (one hardware uint32 division per quotient digit).

On a CPU host, word-domain pipelines short-circuit per call to the NumPy
evaluator when the program is tiny (``_NP_CUTOFF_WIRE_OPS`` wire-words x
ops): for a 2-op bitmap AND over a handful of lanes, one XLA dispatch
costs more than the whole program. On an accelerator they never do.

Programs are frozen/hashable, so compiled pipelines are cached on graph
*structure*: re-recording the same op sequence over new batches reuses the
trace (jax.jit additionally caches per operand shape).

Value semantics: elements are unsigned, width-bit (everything is computed
modulo 2**width — the vertical layout physically holds ``width`` planes).
Opcodes: and/or/xor (plane-wise), add/sub (ripple carry/borrow),
mul (shift-add over the add plane), div/mod (restoring division over the
add/sub planes; lanes dividing by zero yield 0, matching unsigned NumPy),
less (unsigned compare -> 0/1), popcount (adder tree over the element's
planes), reduce_and(param=w) (== mask(w)), reduce_or (!= 0), reduce_xor
(parity).

Tuple op: ``divmod`` runs the restoring divider ONCE and yields the
(quotient, remainder) *pair*; the selector ops ``fst``/``snd`` extract the
components. A tuple value must be consumed through selectors — it can
never itself be a program output. The engine lowers ``div``/``mod``/
``divmod`` through this form, so ``a // b`` and ``a % b`` of the same
operands CSE into one divider pass at flush (the standalone ``div``/
``mod`` opcodes remain valid IR for directly-authored programs).

Word format: a program carries a :class:`~repro.kernels.plane_layout.
PlaneLayout` naming its lane word (32- or 64-bit). Every evaluator is
parameterized over it — SWAR popcount masks, div/mod selector constants
and the width mask derive from the layout instead of being uint32
literals, and the vertical pack/unpack tiles a 64-bit lane as two 32x32
transposes. The pipeline ABI stays flat int32 "wire" arrays
(``layout.wire_words_per_lane`` words per lane) at every layout.

Backend selection goes through the registry in :mod:`repro.backends`
(capability ``"fused"``): on TPU the ``pallas-tpu`` evaluator wins by
priority, elsewhere ``words-cpu``; ``ref-vertical`` is requestable by
name for validation. Backends declare the layouts they consume — the
64-bit evaluators (``words-cpu-64``/``pallas-tpu-64``/``ref-vertical-64``)
and the multi-device ``shard-words`` pipeline are additive
``register_backend`` calls over the same builders.

Before compilation the engine normalizes each recorded graph with
``optimize_program`` (common-subexpression elimination + dead-node/leaf
pruning). The optimizer is a pure function of graph structure, so the
normalized program remains the pipeline-cache key: re-recording the same
op sequence over new batches still hits the cached trace.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.backends import get_backend, select_backend
from repro.kernels import ref
from repro.kernels.bit_transpose import bit_transpose32 as _pl_transpose
from repro.kernels.plane_layout import LAYOUT32, PlaneLayout

LANE = 128
SUBLANE = 8
BLOCK_WORDS = SUBLANE * LANE  # one (8, 128) int32 tile per grid step

OPCODES = ("and", "or", "xor", "add", "sub", "mul", "div", "mod", "divmod",
           "fst", "snd", "less", "popcount", "reduce_and", "reduce_or",
           "reduce_xor")

# Opcodes whose operand order does not matter: CSE canonicalizes their
# argument tuples by sorting so `add(a, b)` and `add(b, a)` unify.
COMMUTATIVE = frozenset({"and", "or", "xor", "add", "mul"})


@dataclasses.dataclass(frozen=True)
class FusedOp:
    """One instruction: ``args`` are value ids in the program's combined id
    space (leaf inputs 0..n_inputs-1, then op results in program order)."""
    opcode: str
    args: tuple[int, ...]
    param: int = 0  # reduce_and: the eager path's mask width w


@dataclasses.dataclass(frozen=True)
class FusedProgram:
    """A straight-line bit-plane program (hashable == pipeline cache key).

    Value-id space: leaf inputs occupy ids ``0..n_inputs-1``; op ``i``'s
    result is id ``n_inputs + i``. ``outputs`` lists the value ids to
    materialize. Values are unsigned width-bit integers; every opcode
    computes modulo ``2**width``. ``layout`` names the lane word format
    the pipeline evaluates in (and is part of the cache key — the same
    op structure compiled at two layouts is two pipelines).
    """
    width: int
    n_inputs: int
    ops: tuple[FusedOp, ...]
    outputs: tuple[int, ...]  # value ids to materialize
    layout: PlaneLayout = LAYOUT32


def optimize_program(program: FusedProgram
                     ) -> tuple[FusedProgram, tuple[int, ...],
                                tuple[int, ...]]:
    """Common-subexpression elimination + dead-node/leaf pruning.

    Returns ``(optimized, out_pos, leaf_map)``:

    * ``optimized`` — the normalized program. Structurally identical
      recordings normalize identically, so it remains a valid pipeline
      cache key (commutative args are sorted, duplicate ops unified,
      unreferenced ops and leaves dropped, ids renumbered densely).
    * ``out_pos`` — for each entry of ``program.outputs``, the index into
      ``optimized.outputs`` holding its value (CSE can map several
      requested outputs onto one computed value).
    * ``leaf_map`` — original leaf ids still used, in the order the
      optimized program expects its inputs.

    The optimizer never changes values (CSE only unifies syntactically
    identical ops, whose results are equal by determinism) and never
    touches the cost plane (the engine charges at record time).

    >>> p = FusedProgram(width=8, n_inputs=2, ops=(
    ...     FusedOp("add", (0, 1)), FusedOp("add", (1, 0)),
    ...     FusedOp("xor", (2, 3)), FusedOp("and", (0, 0))), outputs=(4,))
    >>> opt, out_pos, leaf_map = optimize_program(p)
    >>> len(opt.ops)   # add(1,0) unified with add(0,1); dead and() pruned
    2
    >>> opt.ops[1].args  # xor of the shared add with itself
    (2, 2)
    >>> out_pos, leaf_map
    ((0,), (0, 1))
    """
    return _optimize_cached(program)


@functools.lru_cache(maxsize=512)
def _optimize_cached(program: FusedProgram):
    # Memoized body of optimize_program: programs are frozen/hashable
    # (they already key the pipeline cache) and the result is immutable,
    # so repeat flushes of the same recorded structure skip the whole
    # normalization pass.
    n_in = program.n_inputs
    canon: dict[int, int] = {}     # original op id -> canonical value id
    table: dict[tuple, int] = {}   # (opcode, args, param) -> value id
    kept: list[tuple[int, FusedOp]] = []
    for i, op in enumerate(program.ops):
        vid = n_in + i
        args = tuple(canon.get(a, a) for a in op.args)
        if op.opcode in COMMUTATIVE:
            args = tuple(sorted(args))
        key = (op.opcode, args, op.param)
        prev = table.get(key)
        if prev is not None:
            canon[vid] = prev
        else:
            table[key] = canon[vid] = vid
            kept.append((vid, FusedOp(op.opcode, args, op.param)))
    out_canon = [canon.get(v, v) for v in program.outputs]
    # Narrow each divmod consumed by only one kind of selector into the
    # direct div / mod op: the engine lowers both ``//`` and ``%`` through
    # the shared tuple op, so a program using just one half would
    # otherwise pay for both division passes in every evaluator. Running
    # AFTER unification keeps `a // b; a % b` pairs (CSE merges their two
    # divmod records, giving the pair both selector kinds) on the single
    # divider pass; the orphaned pair falls to the liveness prune below.
    users: dict[int, set] = {}
    for _, op in kept:
        for a in op.args:
            users.setdefault(a, set()).add(op.opcode)
    out_set = set(out_canon)
    pair_args = {vid: op.args for vid, op in kept
                 if op.opcode == "divmod" and vid not in out_set
                 and users.get(vid) in ({"fst"}, {"snd"})}
    if pair_args:
        kept = [(vid, FusedOp("div" if op.opcode == "fst" else "mod",
                              pair_args[op.args[0]]))
                if op.opcode in ("fst", "snd") and op.args[0] in pair_args
                else (vid, op)
                for vid, op in kept]
    needed = set(out_canon)
    for vid, op in reversed(kept):  # backward liveness from the outputs
        if vid in needed:
            needed.update(op.args)
    live = [(vid, op) for vid, op in kept if vid in needed]
    leaf_map = tuple(sorted(v for v in needed if v < n_in))
    remap = {old: new for new, old in enumerate(leaf_map)}
    for j, (vid, _) in enumerate(live):
        remap[vid] = len(leaf_map) + j
    ops = tuple(FusedOp(op.opcode, tuple(remap[a] for a in op.args),
                        op.param) for _, op in live)
    outputs: list[int] = []
    pos_of: dict[int, int] = {}
    out_pos = []
    for v in out_canon:
        rv = remap[v]
        if rv not in pos_of:
            pos_of[rv] = len(outputs)
            outputs.append(rv)
        out_pos.append(pos_of[rv])
    opt = FusedProgram(width=program.width, n_inputs=len(leaf_map),
                       ops=ops, outputs=tuple(outputs),
                       layout=program.layout)
    return opt, tuple(out_pos), leaf_map


def eval_fused_ops(program: FusedProgram, env: list) -> list:
    """Evaluate ``program`` over ``env`` (list of plane-list values, leaves
    first), appending one value per op. Pure jnp on whatever array type the
    planes are — traces identically under jax.jit and inside a Pallas body.
    """
    width = program.width
    zero = jnp.zeros_like(env[0][0])
    for op in program.ops:
        xs = [env[a] for a in op.args]
        env.append(_apply_op(op, xs, width, zero))
    return env


def _apply_op(op: FusedOp, xs: list, width: int, zero):
    def scalar(plane):  # 0/1 result plane -> width-plane value
        return [plane] + [zero] * (width - 1)

    if op.opcode == "and":
        return [a & b for a, b in zip(xs[0], xs[1])]
    if op.opcode == "or":
        return [a | b for a, b in zip(xs[0], xs[1])]
    if op.opcode == "xor":
        return [a ^ b for a, b in zip(xs[0], xs[1])]
    if op.opcode == "add":
        return ref.plane_add(xs[0], xs[1])
    if op.opcode == "sub":
        return ref.plane_sub(xs[0], xs[1])[0]
    if op.opcode == "mul":
        return ref.plane_mul(xs[0], xs[1])
    if op.opcode in ("div", "mod"):
        q, r = ref.plane_divmod(xs[0], xs[1])
        return q if op.opcode == "div" else r
    if op.opcode == "divmod":
        return ref.plane_divmod(xs[0], xs[1])  # tuple value: one divider
    if op.opcode == "fst":
        return xs[0][0]
    if op.opcode == "snd":
        return xs[0][1]
    if op.opcode == "less":
        return scalar(ref.plane_sub(xs[0], xs[1])[1])
    if op.opcode == "popcount":
        counts = ref.plane_popcount(xs[0])
        return (counts + [zero] * width)[:width]
    if op.opcode == "reduce_and":
        # Eager semantics: value == mask(w). Bits below w must all be set,
        # bits at/above w must all be clear (values are width-bit).
        w = min(op.param or width, width)
        if op.param and op.param > width:
            return scalar(zero)  # mask(w) > any width-bit value
        low = ref.plane_reduce(xs[0][:w], "and")
        if w < width:
            low = low & ~ref.plane_reduce(xs[0][w:], "or")
        return scalar(low)
    if op.opcode == "reduce_or":
        return scalar(ref.plane_reduce(xs[0], "or"))
    if op.opcode == "reduce_xor":
        return scalar(ref.plane_reduce(xs[0], "xor"))
    raise KeyError(op.opcode)


# --------------------------------------------------------------------- #
# jnp runner (CPU path / oracle)
# --------------------------------------------------------------------- #


def run_program_ref(program: FusedProgram, x: jax.Array) -> jax.Array:
    """x: [n_inputs, width, W] int32 plane stacks -> [n_out, width, W]."""
    env = [[x[i, j] for j in range(program.width)]
           for i in range(program.n_inputs)]
    env = eval_fused_ops(program, env)
    return jnp.stack([jnp.stack(env[v]) for v in program.outputs])


# --------------------------------------------------------------------- #
# Horizontal word-domain evaluator (CPU execution path)
# --------------------------------------------------------------------- #


def _word_popcount(x, layout: PlaneLayout = LAYOUT32, xp=jnp):
    """SWAR popcount at the layout's word size (Hacker's Delight 5-2);
    masks and the final shift derive from the layout, so the same code
    serves 32- and 64-bit lanes (and NumPy or jnp arrays alike)."""
    m1, m2, m4, h01 = (layout.word_scalar(c, xp)
                       for c in layout.swar_consts)
    x = x - ((x >> 1) & m1)
    x = (x & m2) + ((x >> 2) & m2)
    x = (x + (x >> 4)) & m4
    return (x * h01) >> layout.popcount_shift


def _apply_word_op(op: FusedOp, xs: list, width: int, mask,
                   layout: PlaneLayout, xp):
    dt = layout.dtype_name

    def trunc(v):  # modulo 2**width; free when width fills the word
        return v if mask is None else v & mask

    if op.opcode == "and":
        return xs[0] & xs[1]
    if op.opcode == "or":
        return xs[0] | xs[1]
    if op.opcode == "xor":
        return xs[0] ^ xs[1]
    if op.opcode == "add":
        return trunc(xs[0] + xs[1])
    if op.opcode == "sub":
        return trunc(xs[0] - xs[1])
    if op.opcode == "mul":
        return trunc(xs[0] * xs[1])
    if op.opcode in ("div", "mod", "divmod"):
        # Unsigned NumPy semantics: x // 0 == x % 0 == 0 per lane.
        if xp is np:
            # NumPy integer division BY ZERO already yields 0 (the very
            # semantics the engine exposes), so no masking passes — this
            # is the same errstate idiom the eager dataplane uses.
            with np.errstate(divide="ignore", invalid="ignore"):
                if op.opcode == "div":
                    return xs[0] // xs[1]
                if op.opcode == "mod":
                    return xs[0] % xs[1]
                return (xs[0] // xs[1], xs[0] % xs[1])
        # XLA leaves division by zero undefined: guard the lanes. One
        # hardware division per op — the remainder derives from the
        # quotient (x % y == x - (x // y) * y, exact for unsigned).
        zero_div = xs[1] == 0
        safe = xp.where(zero_div, layout.word_scalar(1, xp), xs[1])
        zero = layout.word_scalar(0, xp)
        q = xs[0] // safe
        if op.opcode == "div":
            return xp.where(zero_div, zero, q)
        r = xs[0] - q * safe
        if op.opcode == "divmod":  # tuple value, consumed by fst/snd
            return (xp.where(zero_div, zero, q),
                    xp.where(zero_div, zero, r))
        return xp.where(zero_div, zero, r)
    if op.opcode == "fst":
        return xs[0][0]
    if op.opcode == "snd":
        return xs[0][1]
    if op.opcode == "less":
        return (xs[0] < xs[1]).astype(dt)
    if op.opcode == "popcount":
        return _word_popcount(xs[0], layout, xp)
    if op.opcode == "reduce_and":
        w = op.param or width
        if w > layout.word_bits:  # mask(w) exceeds any width-bit value
            return xp.zeros_like(xs[0])
        return (xs[0] == layout.word_scalar(layout.mask(w), xp)).astype(dt)
    if op.opcode == "reduce_or":
        return (xs[0] != 0).astype(dt)
    if op.opcode == "reduce_xor":
        return _word_popcount(xs[0], layout, xp) & layout.word_scalar(1, xp)
    raise KeyError(op.opcode)


def run_program_words(program: FusedProgram, leaves: list) -> tuple:
    """Same program, horizontal layout: leaves are flat lane-dtype word
    arrays (element i = word i) of the program's layout, returns one array
    per program output. Operands are masked to ``width`` bits on entry —
    identical value semantics to the vertical evaluators (everything is
    modulo 2**width). Computes with whichever array module the leaves
    belong to: jnp under jit (the 32-bit pipeline), NumPy for the
    small-program short-circuit and as the semantics oracle the
    uint32-pair path (``run_program_pairs``) is tested against."""
    layout = program.layout
    xp = np if isinstance(leaves[0], np.ndarray) else jnp
    # Natural-word programs need no masking at all: every lane op wraps
    # at the word boundary by construction.
    mask = (None if program.width == layout.word_bits
            else layout.word_scalar(layout.mask(program.width), xp))
    env = list(leaves) if mask is None else [x & mask for x in leaves]
    if xp is np:
        # Release each value after its last use (outputs excepted) so
        # the allocator recycles the big intermediate buffers — holding
        # the whole env alive costs fresh pages per op and roughly
        # doubles the evaluator's wall time on full-plane programs.
        # (Under jit env holds tracers; XLA does its own liveness.)
        last_use = {}
        for i, op in enumerate(program.ops):
            for a in op.args:
                last_use[a] = i
        keep = set(program.outputs)
        for i, op in enumerate(program.ops):
            env.append(_apply_word_op(op, [env[a] for a in op.args],
                                      program.width, mask, layout, xp))
            for a in op.args:
                if last_use[a] == i and a not in keep:
                    env[a] = None
        return tuple(env[v] for v in program.outputs)
    for op in program.ops:
        env.append(_apply_word_op(op, [env[a] for a in op.args],
                                  program.width, mask, layout, xp))
    return tuple(env[v] for v in program.outputs)


# --------------------------------------------------------------------- #
# Jitted 64-bit lane path: uint32 (lo, hi) pairs, carry chained in the IR
# --------------------------------------------------------------------- #


def _mulhi32(x, y):
    """High 32 bits of the 64-bit product of two uint32 arrays, via
    16-bit limbs (no uint64 dtype anywhere)."""
    x0, x1 = x & 0xFFFF, x >> 16
    y0, y1 = y & 0xFFFF, y >> 16
    lo_lo = x0 * y0
    mid1 = x1 * y0 + (lo_lo >> 16)
    mid2 = x0 * y1 + (mid1 & 0xFFFF)
    return x1 * y1 + (mid1 >> 16) + (mid2 >> 16)


def _pair_divmod(a, b):
    """Unsigned 64-bit divmod on uint32 (lo, hi) pairs — Knuth Algorithm D
    over base-2^16 digits (Hacker's Delight divmnu): normalize the
    divisor so its top digit has the high bit set, estimate each quotient
    digit with ONE hardware uint32 division, correct it at most twice,
    multiply-subtract in 16-bit digits, add back on the (rare) overdraw.
    Lanes dividing by zero yield (0, 0), matching unsigned NumPy."""
    alo, ahi = a
    blo, bhi = b
    u32 = jnp.uint32
    zero = jnp.zeros_like(alo)
    one = jnp.ones_like(alo)
    bz = (blo | bhi) == 0
    vlo = jnp.where(bz, one, blo)
    vhi = jnp.where(bz, zero, bhi)
    # Normalization shift: clz of the 64-bit divisor (s in [0, 63]).
    s = jnp.where(vhi != 0, jax.lax.clz(vhi),
                  32 + jax.lax.clz(vlo)).astype(u32)
    sl = s & 31
    big = s >= 32
    # Shifts by (32 - sl) are clamped (&31) and gated by sl == 0 selects:
    # XLA leaves out-of-range shift amounts undefined.
    up = jnp.where(sl == 0, zero, vlo >> ((32 - sl) & 31))
    lo_sh = vlo << sl
    hi_sh = (vhi << sl) | up
    vn_lo = jnp.where(big, zero, lo_sh)
    vn_hi = jnp.where(big, lo_sh, hi_sh)
    vn = (vn_lo & 0xFFFF, vn_lo >> 16, vn_hi & 0xFFFF, vn_hi >> 16)
    # Dividend << s as a 128-bit value in four 32-bit words w0..w3.
    a0 = alo << sl
    a1 = (ahi << sl) | jnp.where(sl == 0, zero, alo >> ((32 - sl) & 31))
    a2 = jnp.where(sl == 0, zero, ahi >> ((32 - sl) & 31))
    w0 = jnp.where(big, zero, a0)
    w1 = jnp.where(big, a0, a1)
    w2 = jnp.where(big, a1, a2)
    w3 = jnp.where(big, a2, zero)
    un = [w0 & 0xFFFF, w0 >> 16, w1 & 0xFFFF, w1 >> 16,
          w2 & 0xFFFF, w2 >> 16, w3 & 0xFFFF, w3 >> 16]
    B = 1 << 16
    q = [zero] * 4
    # un[7] < 2^15 <= vn[3] after normalization, so quotient digit 4 is
    # always zero: iterate j = 3..0 only.
    for j in (3, 2, 1, 0):
        num = (un[j + 4] << 16) | un[j + 3]
        qhat = num // vn[3]             # the one hardware division
        rhat = num - qhat * vn[3]
        for _ in range(2):              # Knuth: at most two corrections
            ok = rhat < B
            over = (qhat >= B) | (qhat * vn[2] > ((rhat << 16) | un[j + 2]))
            dec = (ok & over).astype(u32)
            qhat = qhat - dec
            rhat = rhat + vn[3] * dec
        # Multiply-subtract qhat * vn from un[j..j+4] in 16-bit digits;
        # borrows ride the uint32 wraparound (t's top bits encode the
        # signed borrow because |t| < 2^17).
        k = zero
        for i in range(4):
            p = qhat * vn[i]
            t = un[i + j] - k - (p & 0xFFFF)
            un[i + j] = t & 0xFFFF
            k = (p >> 16) + ((B - (t >> 16)) & 0xFFFF)
        t = un[j + 4] - k
        neg = t >> 31                   # borrow out: qhat was one too big
        negb = neg.astype(bool)
        q[j] = qhat - neg
        c = zero
        for i in range(4):              # add-back, selected where needed
            w = un[i + j] + vn[i] + c
            un[i + j] = jnp.where(negb, w & 0xFFFF, un[i + j])
            c = w >> 16
        un[j + 4] = jnp.where(negb, (t + c) & 0xFFFF, t & 0xFFFF)
    # Remainder: un[0..3] denormalized by s; quotient digits q[0..3].
    r_lo_n = un[0] | (un[1] << 16)
    r_hi_n = un[2] | (un[3] << 16)
    down = jnp.where(sl == 0, zero, r_hi_n << ((32 - sl) & 31))
    rlo_s = (r_lo_n >> sl) | down
    rhi_s = r_hi_n >> sl
    quo = (jnp.where(bz, zero, q[0] | (q[1] << 16)),
           jnp.where(bz, zero, q[2] | (q[3] << 16)))
    rem = (jnp.where(bz, zero, jnp.where(big, rhi_s, rlo_s)),
           jnp.where(bz, zero, jnp.where(big, zero, rhi_s)))
    return quo, rem


def _apply_pair_op(op: FusedOp, xs: list, width: int, mask, layout):
    """One opcode on uint32 (lo, hi) pair values — the 64-bit-lane mirror
    of ``_apply_word_op`` (identical value semantics, pinned by tests)."""
    u32 = jnp.uint32

    def trunc(lo, hi):  # modulo 2**width; free at the natural word
        return (lo, hi) if mask is None else (lo & mask[0], hi & mask[1])

    if op.opcode == "and":
        return (xs[0][0] & xs[1][0], xs[0][1] & xs[1][1])
    if op.opcode == "or":
        return (xs[0][0] | xs[1][0], xs[0][1] | xs[1][1])
    if op.opcode == "xor":
        return (xs[0][0] ^ xs[1][0], xs[0][1] ^ xs[1][1])
    if op.opcode == "add":
        (alo, ahi), (blo, bhi) = xs[0], xs[1]
        slo = alo + blo
        return trunc(slo, ahi + bhi + (slo < alo).astype(u32))
    if op.opcode == "sub":
        (alo, ahi), (blo, bhi) = xs[0], xs[1]
        return trunc(alo - blo, ahi - bhi - (alo < blo).astype(u32))
    if op.opcode == "mul":
        (alo, ahi), (blo, bhi) = xs[0], xs[1]
        hi = _mulhi32(alo, blo) + alo * bhi + ahi * blo  # mod-2^64 high
        return trunc(alo * blo, hi)
    if op.opcode in ("div", "mod", "divmod"):
        q, r = _pair_divmod(xs[0], xs[1])
        if op.opcode == "div":
            return q
        if op.opcode == "mod":
            return r
        return (q, r)  # tuple value, consumed by fst/snd
    if op.opcode == "fst":
        return xs[0][0]
    if op.opcode == "snd":
        return xs[0][1]
    zero = jnp.zeros_like(xs[0][0])
    if op.opcode == "less":
        (alo, ahi), (blo, bhi) = xs[0], xs[1]
        lt = (ahi < bhi) | ((ahi == bhi) & (alo < blo))
        return (lt.astype(u32), zero)
    if op.opcode == "popcount":
        lo, hi = xs[0]
        pc = (_word_popcount(lo, LAYOUT32, jnp)
              + _word_popcount(hi, LAYOUT32, jnp))
        return (pc, zero)
    if op.opcode == "reduce_and":
        w = op.param or width
        if w > layout.word_bits:  # mask(w) exceeds any width-bit value
            return (zero, zero)
        lo, hi = xs[0]
        mlo = (1 << min(w, 32)) - 1
        mhi = 0 if w <= 32 else (1 << (w - 32)) - 1
        eq = (lo == u32(mlo)) & (hi == u32(mhi))
        return (eq.astype(u32), zero)
    if op.opcode == "reduce_or":
        lo, hi = xs[0]
        return (((lo | hi) != 0).astype(u32), zero)
    if op.opcode == "reduce_xor":
        lo, hi = xs[0]
        return (_word_popcount(lo ^ hi, LAYOUT32, jnp) & u32(1), zero)
    raise KeyError(op.opcode)


def run_program_pairs(program: FusedProgram, leaves: list) -> tuple:
    """The jitted 64-bit lane path: each flat int32 wire leaf (lo, hi
    interleaved little-endian) deinterleaves into a uint32 (lo, hi) pair,
    the whole program evaluates on pairs with carries chained across the
    pair in every arithmetic op, and outputs re-interleave to wire. Pure
    jnp — one fused elementwise DAG under jax.jit, no uint64 dtype (so no
    global x64 flag), bit-exact against ``run_program_words`` (tests)."""
    layout = program.layout
    width = program.width
    mask = None
    if width < layout.word_bits:
        mask = (jnp.asarray((1 << min(width, 32)) - 1, jnp.uint32),
                jnp.asarray(0 if width <= 32 else (1 << (width - 32)) - 1,
                            jnp.uint32))
    env = []
    for w in leaves:
        v = jax.lax.bitcast_convert_type(jnp.asarray(w),
                                         jnp.uint32).reshape(-1, 2)
        lo, hi = v[:, 0], v[:, 1]
        env.append((lo, hi) if mask is None
                   else (lo & mask[0], hi & mask[1]))
    for op in program.ops:
        env.append(_apply_pair_op(op, [env[a] for a in op.args],
                                  width, mask, layout))
    outs = []
    for vid in program.outputs:
        lo, hi = env[vid]
        wire = jnp.stack([lo, hi], axis=-1).reshape(-1)
        outs.append(jax.lax.bitcast_convert_type(wire, jnp.int32))
    return tuple(outs)


# --------------------------------------------------------------------- #
# Pallas variant (BLOCK_WORDS tiling, whole program per VMEM block)
# --------------------------------------------------------------------- #


# VMEM of the Pallas program kernel. Its block holds every plane of every
# leaf and every output for one (8, 128) int32 tile, and the pipeline
# double-buffers it, so a block grows with values x width; Mosaic's default
# scoped limit (16 MiB on v5e) refuses one of 64 leaves x 32 planes. The
# kernel asks for what its block needs plus that default as headroom (the
# program's intermediates do not count against the limit), and the
# engine's auto-flush keeps every block within VMEM_BLOCK_BUDGET, so the
# request stays under v5e's 128 MiB of physical VMEM.
TILE_BYTES = BLOCK_WORDS * 4
VMEM_BLOCK_BUDGET = 64 << 20
_VMEM_HEADROOM = 16 << 20


def block_bytes(n_values: int, width: int) -> int:
    """Double-buffered VMEM block of ``n_values`` values of ``width``
    planes in the Pallas program kernel."""
    return 2 * n_values * width * TILE_BYTES


def _program_kernel(x_ref, o_ref, *, program: FusedProgram):
    env = [[x_ref[i, j] for j in range(program.width)]
           for i in range(program.n_inputs)]
    env = eval_fused_ops(program, env)
    for t, vid in enumerate(program.outputs):
        for j in range(program.width):
            o_ref[t, j] = env[vid][j]


@functools.partial(jax.jit, static_argnames=("program", "interpret"))
def run_program_pallas(program: FusedProgram, x: jax.Array,
                       interpret: bool = False) -> jax.Array:
    """Pallas execution of ``run_program_ref``: same [n_in, width, W] ->
    [n_out, width, W] contract, program evaluated per (8, 128) block."""
    n_in, width, w = x.shape
    pad = (-w) % BLOCK_WORDS
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, pad))).astype(jnp.int32)
    blocks = xp.shape[2] // BLOCK_WORDS
    xb = xp.reshape(n_in, width, blocks, SUBLANE, LANE)
    n_out = len(program.outputs)
    out = pl.pallas_call(
        functools.partial(_program_kernel, program=program),
        grid=(blocks,),
        in_specs=[pl.BlockSpec((n_in, width, 1, SUBLANE, LANE),
                               lambda i: (0, 0, i, 0, 0))],
        out_specs=pl.BlockSpec((n_out, width, 1, SUBLANE, LANE),
                               lambda i: (0, 0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_out, width, blocks, SUBLANE, LANE),
                                       jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=block_bytes(n_in + n_out, width)
            + _VMEM_HEADROOM),
    )(xb)
    return out.reshape(n_out, width, blocks * BLOCK_WORDS)[:, :, :w] \
        .astype(x.dtype)


# --------------------------------------------------------------------- #
# End-to-end pipeline: pack -> run -> unpack, one jit trace, cached.
# Evaluator chosen by capability lookup in the repro.backends registry.
# --------------------------------------------------------------------- #


def get_pipeline(program: FusedProgram, force_pallas: bool = False,
                 interpret: bool = False, force_vertical: bool = False,
                 donate: bool = False, backend: str | None = None):
    """Compiled callable for ``program``: ``fn(*leaves) -> tuple(outs)``.

    Leaves are flat int32 *wire* arrays of packed horizontal words
    (``program.layout.wire_words_per_lane`` int32 words per lane, lane
    count a multiple of 32); outputs likewise. One jit trace end to
    end. The evaluator is resolved through the backend registry
    (``repro.backends``, capability ``"fused"``, filtered by the
    program's layout): on TPU the Pallas vertical evaluator wins
    (operands bit-transpose once, the fused program runs per VMEM block,
    outputs transpose back once); elsewhere the word-domain evaluator
    runs. ``backend=`` names a registered evaluator explicitly;
    ``force_pallas``/``force_vertical`` are shorthands for the built-in
    names at the program's layout. With ``donate=True`` the leaf
    device buffers are donated to the trace (``donate_argnums``) so XLA
    may reuse them for intermediates — the engine's leaf snapshots stay on
    the host, so donation never invalidates caller-visible data. Cached
    on (program structure, backend, donate); jit handles per-shape
    specialization.
    """
    wb = program.layout.word_bits
    if backend is None:
        if force_pallas:
            backend = "pallas-tpu" if wb == 32 else f"pallas-tpu-{wb}"
        elif force_vertical:
            backend = "ref-vertical" if wb == 32 else f"ref-vertical-{wb}"
        else:
            backend = select_backend(require="fused", width=program.width,
                                     layout=program.layout).name
    spec = get_backend(backend)
    if wb not in spec.layouts:
        raise ValueError(
            f"backend {backend!r} does not support the {wb}-bit plane "
            f"layout (declares {sorted(spec.layouts)})")
    # Cache on the resolved BackendSpec, not the name: re-registering a
    # name creates a new (frozen, hashable) spec, so stale pipelines
    # compiled by a replaced builder can never be served.
    return _cached_pipeline(program, spec, interpret, donate)


@functools.lru_cache(maxsize=256)  # bounded: one jit callable per structure
def _cached_pipeline(program: FusedProgram, spec, interpret: bool,
                     donate: bool):
    return spec.builder(program, interpret=interpret, donate=donate)


def with_fault_injection(pipeline, injector):
    """Fault-injection hook over a compiled pipeline.

    ``injector(outs) -> outs`` receives the tuple of clean wire outputs
    after each execution and returns the outputs to hand to the caller —
    the reliability plane (``repro.reliability``) uses this to derive
    fault-injected replicas from the clean run, majority-vote them, and
    retry on weak margins. The wrapper is built per flush only when
    injection is enabled, so the disabled path still calls the cached
    pipeline directly (zero overhead, same object identity for the
    pipeline cache).
    """
    def injected(*leaves):
        return injector(pipeline(*leaves))

    return injected


def _donating(fn, n_leaves: int):
    """Wrap a jit'd pipeline so its leaf buffers are donated: operands are
    committed to the device first (donating raw NumPy args would fall back
    to a copy with a warning), then handed over for XLA to reuse. Donation
    is opportunistic — a program usually has fewer outputs than leaves, so
    some donated buffers go unused; jax's warning about those is expected
    and silenced."""
    jitted = jax.jit(fn, donate_argnums=tuple(range(n_leaves)))

    def call(*leaves):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return jitted(*(jnp.asarray(x) for x in leaves))

    return call


# Per-call NumPy short-circuit threshold for word pipelines on a CPU
# host, in wire-words x ops: below it, XLA dispatch overhead (which grows
# with the leaf count — each argument is canonicalized and placed) costs
# more than evaluating the whole program in NumPy with last-use buffer
# recycling (a k-clique AND pair over a few lanes is ~100 wire-ops and
# stays NumPy; the paper-scale 30-leaf BMI scan is ~10^7 wire-ops and
# the 2M-lane prog16 staple is ~10^7 — both win jitted, where XLA's
# one-pass loop fusion replaces ~n_ops full-array traversals with one).
# Read at call time so tests can pin either path.
_NP_CUTOFF_WIRE_OPS = 1 << 20


def build_words_pipeline(program: FusedProgram, donate: bool = False):
    """Word-domain pipeline: the bracketing transpose pair cancels
    algebraically, so the program fuses directly on horizontal words —
    one jax.jit trace at EVERY layout, on whatever device JAX runs on.
    32-bit lanes evaluate on uint32 words; 64-bit lanes evaluate as
    uint32 (lo, hi) pairs (``run_program_pairs``, carry chained across
    the pair in the IR), so ``donate`` works at both layouts.

    Where JAX's default backend is the CPU, two cases short-circuit to
    the NumPy word evaluator instead: tiny programs, per call
    (``_NP_CUTOFF_WIRE_OPS``), and 64-bit programs containing division —
    x86 has no SIMD integer divide, so the pair evaluator's Knuth long
    division scalarizes the fused XLA loop (~100 elementwise passes per
    divmod), while NumPy's hardware 64-bit ``divq`` is one pass. Both
    reasons are facts about the host CPU, so on an accelerator every
    call runs jitted on the device."""
    layout = program.layout
    n_ops = max(1, len(program.ops))
    host_cpu = jax.default_backend() == "cpu"
    np_div64 = host_cpu and layout.word_bits == 64 and any(
        op.opcode in ("div", "mod", "divmod") for op in program.ops)

    if layout.word_bits == 32:
        def core(*leaves):
            outs = run_program_words(
                program,
                [jax.lax.bitcast_convert_type(x, jnp.uint32)
                 for x in leaves])
            return tuple(jax.lax.bitcast_convert_type(o, jnp.int32)
                         for o in outs)
    else:
        def core(*leaves):
            return run_program_pairs(program, leaves)

    jitted = (_donating(core, program.n_inputs) if donate
              else jax.jit(core))

    def np_words(*leaves):
        outs = run_program_words(
            program, [layout.from_wire(np.asarray(x)) for x in leaves])
        return tuple(layout.to_wire(o) for o in outs)

    def small(wire_words):
        return host_cpu and wire_words * n_ops <= _NP_CUTOFF_WIRE_OPS

    def word_pipeline(*leaves):
        if np_div64:
            return np_words(*leaves)
        if leaves and small(leaves[0].size) \
                and all(isinstance(x, np.ndarray) for x in leaves):
            return np_words(*leaves)
        return jitted(*leaves)

    # Leaf-cache protocol (engine._resolve_cached_leaves): cached device
    # buffers are only worth serving when the call will actually run
    # jitted — and never into a donating trace.
    word_pipeline.wants_device = (
        lambda wire_words: not donate and not np_div64
        and not small(wire_words))
    return word_pipeline


def build_sharded_words_pipeline(program: FusedProgram,
                                 donate: bool = False):
    """Multi-device word-domain pipeline (``shard-words``): the program's
    word axis partitions across ``jax.devices()`` on a 1-D ``("words",)``
    mesh, so ONE flush executes one program on every local device. The
    program is elementwise across words, so the sharding is
    communication-free — GSPMD places each shard's slice of the fused
    elementwise DAG on its device; outputs gather on read-back.

    Leaves pad to a multiple of 32 x n_devices before placement. Outputs
    stay on the devices: sharded when no padding was needed, otherwise
    sliced back to the leaves' length on the device. ``donate`` is
    ignored: donated input buffers would alias the per-device shards the
    caller still owns.
    """
    from repro.distributed.sharding import words_mesh, words_sharding

    if program.layout.word_bits != 32:
        raise ValueError("shard-words shards the 32-bit word layout; "
                         "register a 64-bit variant to widen it")
    sharding = words_sharding(words_mesh())
    n_dev = sharding.mesh.size

    def word_pipeline(*leaves):
        outs = run_program_words(
            program,
            [jax.lax.bitcast_convert_type(x, jnp.uint32)
             for x in leaves])
        return tuple(jax.lax.bitcast_convert_type(o, jnp.int32)
                     for o in outs)

    jitted = jax.jit(word_pipeline)

    def sharded_pipeline(*leaves):
        n = np.asarray(leaves[0]).shape[0]
        pad = (-n) % (32 * n_dev)
        placed = [jax.device_put(np.pad(np.asarray(x, np.int32), (0, pad)),
                                 sharding) for x in leaves]
        outs = jitted(*placed)
        return outs if not pad else tuple(o[:n] for o in outs)

    return sharded_pipeline


def build_vertical_pipeline(program: FusedProgram, use_pallas: bool,
                            interpret: bool = False, donate: bool = False):
    """Vertical bit-plane pipeline: transpose in once, run the fused
    program (Pallas kernel or jnp oracle), transpose out once. The
    layout's pack/unpack maps horizontal wire words onto ``width`` bit
    planes — a 64-bit lane is two stacked 32x32 transpose tiles, so the
    one 32x32 transpose kernel serves every layout."""
    width = program.width
    layout = program.layout
    if use_pallas:
        transpose = functools.partial(_pl_transpose, interpret=interpret)
        run = functools.partial(run_program_pallas, program,
                                interpret=interpret)
    else:
        transpose = ref.bit_transpose32
        run = functools.partial(run_program_ref, program)

    def pipeline(*leaves):
        stack = jnp.stack([layout.pack_planes(leaf, transpose, width)
                           for leaf in leaves])
        outs = run(stack)
        return tuple(layout.unpack_planes(outs[t], transpose, width)
                     for t in range(outs.shape[0]))

    fn = _donating(pipeline, program.n_inputs) if donate \
        else jax.jit(pipeline)

    def vertical_pipeline(*leaves):
        return fn(*leaves)

    # Leaf-cache protocol: the vertical path is always jitted, so cached
    # device buffers are always worth serving (unless donating).
    vertical_pipeline.wants_device = lambda wire_words: not donate
    return vertical_pipeline
