"""Expert routing: of the token choices that the rows decoding in the
window make over every MoE layer, the share that lands on an expert this
chip holds (the engine's counters ``moe_choices_held`` over
``moe_choices_routed``), in %.  Even routing over 64 experts of which 8
are held reads 12.5%.  Nothing where the engine keeps no such counter."""


def read(win):
    if "moe_choices_routed" not in win.stats_open:
        return None
    n = win.delta("moe_choices_routed")
    return 100.0 * win.delta("moe_choices_held") / n if n else None
