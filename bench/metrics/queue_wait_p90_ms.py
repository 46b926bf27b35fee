"""Scheduler: due time to admission into a slot (the engine's
``Session.t_admit``), 90th percentile over the requests due in the window.
A request not admitted by the close enters at its age at the close."""

import numpy as np


def read(win):
    waits = []
    for r in win.due_in_window():
        t = r.t_admit if r.t_admit is not None else win.t_close
        waits.append((min(t, win.t_close) - r.due) * 1e3)
    return float(np.percentile(waits, 90)) if waits else None
