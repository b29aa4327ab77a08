"""Device idle milliseconds per ``Engine.decode_chunk`` call in the window
while the engine's own host code runs: idle gaps whose innermost host span
is an ``engine.*`` span (``scopes.idle_by_span``), over the decode calls.
Nothing to read from an engine without spans."""
import scopes


def read(ctx):
    found = scopes.analyze(ctx)
    calls = len(ctx["record"]["chunks"])
    if not found["engine_spans"] or not found["idle"] or not calls:
        return None
    idle = sum(v for k, v in found["idle"].items()
               if k.startswith(scopes.ENGINE))
    return 1e3 * idle / calls
