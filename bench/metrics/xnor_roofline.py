"""XNOR linear kernel: its least time in the decode program, from shapes
(``roofline.xnor_step_floor_s`` at one row per slot), over the device time
of its calls there, from the trace."""

import roofline

PROGRAM = "decode_fn"
KERNEL = "xnor_linear_fused"


def read(win):
    if not win.trace:
        return None
    execs = sum(v["count"] for k, v in win.trace["programs"].items()
                if PROGRAM in k)
    ops = [v for p, d in win.trace["per_program"].items() if PROGRAM in p
           for k, v in d.items() if KERNEL in k]
    spent = sum(v["seconds"] for v in ops)
    if not execs or not spent:
        return None
    floor = execs * roofline.xnor_step_floor_s(
        win.dims, win.geometry["slots"], win.peaks)
    return 100.0 * floor / spent
