"""The fused evaluator a flush runs on: ``backends.fused_evaluator``.

The host facts the choice reads (``on_tpu``, ``multi_device``,
``device_memory``) are stood in for, so every row of the table runs on
this CPU host: one or four devices, on or off a TPU, both plane layouts,
leaves that fit one chip or outgrow it, and a pinned name.
"""

import pytest

from repro import backends

GIB = 1 << 30
V5E_LIMIT = int(15.75 * GIB)  # one v5e chip's bytes_limit
FITS = int(3.75 * GIB)        # 2^30 users: one chip's plan holds it
OUTGROWS = 15 * GIB           # 2^32 users: past one chip


@pytest.mark.parametrize(
    "tpu, devices, word_bits, leaf_bytes, pinned, want", [
        # Off a TPU: the word path, sharded over a multi-device host at
        # 32 bits (shard-words runs no 64-bit layout).
        (False, 1, 32, 0, None, "words-cpu"),
        (False, 1, 64, 0, None, "words-cpu"),
        (False, 4, 32, 0, None, "shard-words"),
        (False, 4, 64, 0, None, "words-cpu"),
        # One chip: Pallas, whatever the size (nowhere to shard).
        (True, 1, 32, FITS, None, "pallas-tpu"),
        (True, 1, 32, OUTGROWS, None, "pallas-tpu"),
        (True, 1, 64, OUTGROWS, None, "pallas-tpu"),
        # Four chips: Pallas, unless a 32-bit flush outgrows one chip.
        (True, 4, 32, 0, None, "pallas-tpu"),
        (True, 4, 32, FITS, None, "pallas-tpu"),
        (True, 4, 32, OUTGROWS, None, "shard-words"),
        (True, 4, 64, FITS, None, "pallas-tpu"),
        (True, 4, 64, OUTGROWS, None, "pallas-tpu"),
        # A pinned name wins, over the platform and the size rule.
        (False, 1, 32, 0, "ref-vertical", "ref-vertical"),
        (False, 1, 64, 0, "ref-vertical", "ref-vertical"),
        (True, 4, 32, OUTGROWS, "pallas-tpu", "pallas-tpu"),
        (True, 4, 32, FITS, "shard-words", "shard-words"),
        (True, 1, 64, 0, "words-cpu", "words-cpu"),
        # ... and refuses a layout it cannot run.
        (True, 4, 64, OUTGROWS, "shard-words", ValueError),
        (False, 4, 64, 0, "shard-words", ValueError),
    ])
def test_fused_evaluator_choice(monkeypatch, tpu, devices, word_bits,
                                leaf_bytes, pinned, want):
    chip_bytes = V5E_LIMIT if tpu else None  # the CPU reports no limit
    monkeypatch.setattr(backends, "on_tpu", lambda: tpu)
    monkeypatch.setattr(backends, "multi_device", lambda: devices > 1)
    monkeypatch.setattr(backends, "device_memory",
                        lambda: (devices, chip_bytes))
    if want is ValueError:
        with pytest.raises(ValueError, match="64-bit"):
            backends.fused_evaluator(word_bits, leaf_bytes, pinned)
    else:
        assert backends.fused_evaluator(word_bits, leaf_bytes,
                                        pinned) == want
