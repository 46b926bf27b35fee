"""Device: the share of the traced window in which no operation ran on
the chip (1 - busy union / window, from the trace)."""


def read(win):
    if not win.trace or not win.trace["window_s"]:
        return None
    return 100.0 * (1.0 - win.trace["busy_s"] / win.trace["window_s"])
