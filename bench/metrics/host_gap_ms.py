"""Engine host: the engine's own host time per turn in the window, from a
decode step's token read to the next decode dispatch with the time between
its steps (the harness's loop, the profiler's start and stop) left out: the
engine's counters ``host_turn_ns`` over ``host_turns``, in ms.  Nothing
where the engine keeps no such counter."""


def read(win):
    if "host_turns" not in win.stats_open:
        return None
    n = win.delta("host_turns")
    return 1e-6 * win.delta("host_turn_ns") / n if n else None
