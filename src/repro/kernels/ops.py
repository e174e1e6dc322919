"""Jit'd dispatch wrappers for the Pallas kernels.

On TPU the Pallas path runs natively; elsewhere the wrappers dispatch to the
jnp oracle by default. ``force_pallas=True`` off the TPU needs
``interpret=True`` as well: Pallas interpret mode executes the kernel body
in Python per grid step and is for validation, not speed, so it runs only
when the caller asks for it (tests/kernels/ do).

Layout conventions (docs/architecture.md): *horizontal* operands are flat
packed words (element i = word i); *vertical* operands are bit-plane
stacks ``[width, W]`` where plane j holds bit j of every element
(``bit_transpose32`` converts 32x32 tiles between the two). ``maj_n`` /
``bitserial_add`` / ``run_fused_program`` operate on vertical planes;
values are unsigned modulo 2**width.
"""

from __future__ import annotations

from repro.backends import on_tpu as _on_tpu
from repro.kernels import ref
from repro.kernels.bit_transpose import bit_transpose32 as _pl_transpose
from repro.kernels.bitserial_add import bitserial_add as _pl_add
from repro.kernels.charge_share import charge_share as _pl_cs
from repro.kernels.fused_program import (FusedProgram, run_program_pallas,
                                         run_program_ref)
from repro.kernels.maj_n import maj_n as _pl_maj


def maj_n(x, threshold: int, force_pallas: bool = False,
          interpret: bool = False):
    if _on_tpu() or force_pallas:
        return _pl_maj(x, threshold, interpret=interpret)
    # CPU: the bit-sliced form beats the unpack-sum oracle ~20x (§Perf K0).
    return ref.maj_n_fast(x, threshold)


def bitserial_add(a, b, force_pallas: bool = False, interpret: bool = False):
    if _on_tpu() or force_pallas:
        return _pl_add(a, b, interpret=interpret)
    return ref.bitserial_add(a, b)


def bit_transpose32(x, force_pallas: bool = False, interpret: bool = False):
    if _on_tpu() or force_pallas:
        return _pl_transpose(x, interpret=interpret)
    return ref.bit_transpose32(x)


def charge_share(v, caps, *, vdd: float, c_bl: float,
                 force_pallas: bool = False, interpret: bool = False):
    if _on_tpu() or force_pallas:
        return _pl_cs(v, caps, vdd=vdd, c_bl=c_bl, interpret=interpret)
    return ref.charge_share(v, caps, vdd=vdd, c_bl=c_bl)


def run_fused_program(program: FusedProgram, x, force_pallas: bool = False,
                      interpret: bool = False):
    """Evaluate a fused program on *vertical plane stacks*: x [n_in, width,
    W] int32 -> [n_out, width, W]. Like the other wrappers here, the CPU
    fallback is the jnp oracle (validation form). Callers holding flat
    horizontal operands — the engine's flush() — should use
    ``fused_program.get_pipeline`` instead: on CPU it switches to the
    word-domain evaluator, which is the actual speed path."""
    if _on_tpu() or force_pallas:
        return run_program_pallas(program, x, interpret=interpret)
    return run_program_ref(program, x)
