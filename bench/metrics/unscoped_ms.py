"""Device milliseconds per decode step that the decode-chunk programs spend
in ops outside every named scope of the program (``scopes.py``): the layer
scan's slicing and updating of the stacked weights and KV pools, and XLA's
loop-carry copies.  Nothing to read from a program without scopes."""
import scopes


def read(ctx):
    found = scopes.analyze(ctx)
    steps = scopes.decode_steps(ctx)
    if not found["scoped"] or not steps:
        return None
    return 1e3 * found["decode"].get(scopes.UNSCOPED, 0.0) / steps
