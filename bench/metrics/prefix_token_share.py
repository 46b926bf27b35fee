"""Prefix cache: prompt tokens served from cached blocks, as a share of the
prompt tokens admitted in the window (the engine's counters)."""


def read(win):
    prompt = win.delta("prompt_tokens")
    if not prompt:
        return None
    return 100.0 * win.delta("prefix_tokens") / prompt
