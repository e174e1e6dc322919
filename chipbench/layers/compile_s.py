"""Pipeline build at set-up (JAX tracing, lowering and backend compile,
persistent-cache loads included): wall seconds the process spent
compiling, read from the program's process-wide ``compile.s`` counter
(``repro.telemetry.process_counters``). Set-up warms every shape, so
the window adds nothing (``compiles_in_window`` reads 0): the number is
the set-up's. A program without the counter reports nothing."""


def read(w):
    if w.spans is None:
        return None
    try:
        from repro.telemetry import process_counters
    except ImportError:
        return None
    c = process_counters()
    return c["compile.s"] if "compile.s" in c else None
