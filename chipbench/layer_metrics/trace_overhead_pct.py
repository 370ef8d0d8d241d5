"""trace_overhead_pct: what the capture costs while it is on: 100 x (1 - the
traced tail's rate over the measured window's rate), both of one ``--trace 2``
process on the same traffic. The tail is a fifth of the window, so it reads
within the cells' run-to-run noise of zero where the capture costs nothing;
a negative value is that noise. None outside ``--trace 2``."""


def read(obs):
    measured = obs.get("measured")
    if not measured or not measured.get("window_s") or not measured.get("tokens"):
        return None
    if not obs.get("window_s"):
        return None
    rate, base = obs["tokens"] / obs["window_s"], measured["tokens"] / measured["window_s"]
    return 100.0 * (1.0 - rate / base)
