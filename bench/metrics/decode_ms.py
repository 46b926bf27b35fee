"""Model step: device time per execution of the decode program, from the
trace (mean over the executions in the traced window)."""

PROGRAM = "decode_fn"


def read(win):
    if not win.trace:
        return None
    hits = [v for k, v in win.trace["programs"].items() if PROGRAM in k]
    n = sum(v["count"] for v in hits)
    return 1e3 * sum(v["seconds"] for v in hits) / n if n else None
