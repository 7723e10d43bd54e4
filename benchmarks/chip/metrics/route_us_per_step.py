"""Device time per simulated step under the ``fabric/inject/route`` scope
(routing-LUT gathers, deadlines), from the traced window (``scope_s`` of
``scopes.py``); 0 where the trace holds none."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["steps"] or "scope_s" not in trace:
        return None
    return trace["scope_s"].get("fabric/inject/route", 0.0) / trace["steps"] * 1e6
