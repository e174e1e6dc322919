"""Device layer: the share of the traced window in which no operation ran
on the device, in percent (1 - busy / window, from the device trace)."""


def read(w):
    t = w.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
