"""Paged decode attention kernel: its least time in the decode program,
every live key and value read once (``roofline.paged_attn_floor_s`` at the
window's mean live context per step, from the harness's decode log), over
the device time of its calls there, from the trace."""

import roofline

PROGRAM = "decode_fn"
KERNEL = "paged_decode_attention"


def read(win):
    if not win.trace:
        return None
    log = [(c, n) for t, c, n in win.decode_log
           if win.t_open < t <= win.t_close]
    execs = sum(v["count"] for k, v in win.trace["programs"].items()
                if PROGRAM in k)
    ops = [v for p, d in win.trace["per_program"].items() if PROGRAM in p
           for k, v in d.items() if KERNEL in k]
    spent = sum(v["seconds"] for v in ops)
    if not log or not execs or not spent:
        return None
    ctx = sum(c for c, _ in log) / len(log)
    rows = sum(n for _, n in log) / len(log)
    floor = execs * roofline.paged_attn_floor_s(win.dims, ctx, rows,
                                                win.peaks)
    return 100.0 * floor / spent
