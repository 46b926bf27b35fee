"""Paged attention kernel: of the table blocks its walk reads for the rows
that decode in the window, the share that holds an attended key (the
engine's counters ``decode_kv_blocks_live`` over ``decode_kv_blocks_read``),
in %.  Nothing where the engine keeps no such counter."""


def read(win):
    if "decode_kv_blocks_read" not in win.stats_open:
        return None
    n = win.delta("decode_kv_blocks_read")
    return 100.0 * win.delta("decode_kv_blocks_live") / n if n else None
