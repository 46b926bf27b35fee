"""Whole step: model operations completed in the window over the window's
length and the chip's bf16 peak.  Tokens: every decoded token and every
prefill-chunk row (``prefill_chunks`` x chunk size, the last chunk's pad rows
included); operations: ``roofline.model_ops``, with the attention of decoded
tokens at their live context and that of prefill rows left out, so the
share is never overstated.  Prefix-cached prompt tokens are not computed."""

import roofline


def read(win):
    log = [(c, n) for t, c, n in win.decode_log
           if win.t_open < t <= win.t_close]
    decoded = sum(n for _, n in log)
    rows = win.delta("prefill_chunks") * win.geometry["prefill_chunk"]
    if not decoded + rows:
        return None
    ops = roofline.model_ops(win.dims, decoded + rows,
                             sum(c for c, _ in log))
    return 100.0 * ops / (win.seconds * win.peaks["bf16_flops_per_s"])
