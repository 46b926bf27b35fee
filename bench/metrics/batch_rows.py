"""Scheduler: rows that decoded a token per decode step in the window (the
engine's counters ``decode_rows`` over ``decode_steps``; a row still
mid-prefill that rides along is not one).  Nothing where the engine keeps
no such counter."""


def read(win):
    if "decode_rows" not in win.stats_open:
        return None
    n = win.delta("decode_steps")
    return win.delta("decode_rows") / n if n else None
