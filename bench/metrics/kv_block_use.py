"""KV block pool: of the blocks live requests hold at each decode step in
the window, the share that holds a written token (the engine's counters
``kv_live_block_steps`` over ``kv_reserved_block_steps``), in %.  Nothing
where the engine keeps no such counter."""


def read(win):
    if "kv_reserved_block_steps" not in win.stats_open:
        return None
    n = win.delta("kv_reserved_block_steps")
    return 100.0 * win.delta("kv_live_block_steps") / n if n else None
