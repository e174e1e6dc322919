"""Evaluator layer (``kernels/fused_program.py``, the backend chosen):
the share of the HBM roofline the device reached, in percent. The least
time is the user-data bytes of the window's queries (from the query
shapes, not from leaves or transposes) over the chip's peak HBM bytes per
second; it is divided by the device busy time of the traced window."""


def read(w):
    if w.trace is None or w.trace["busy_s"] <= 0 or not w.n_queries:
        return None
    least_s = sum(q.nbytes for q in w.answered) / w.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / w.trace["busy_s"]
