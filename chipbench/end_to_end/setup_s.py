"""Seconds from the start of the process to the start of the window:
imports, the chip's start, data made from the seed, the device, and the
warm-up of every shape the traffic uses (compilation, where the cache
misses)."""


def read(w):
    return w.setup_s
