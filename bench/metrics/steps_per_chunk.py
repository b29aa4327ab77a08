"""Decode steps per ``Engine.decode_chunk`` call in the window (a count from
the engine's own counters): a chunk shrinks to the largest power of two
that no active request overruns."""


def read(ctx):
    eng = ctx["record"]["engine"]
    if not eng["decode_calls"]:
        return None
    return eng["decode_steps"] / eng["decode_calls"]
