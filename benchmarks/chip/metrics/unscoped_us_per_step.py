"""Device time per simulated step of the program ops that carry no
``op_name`` and have no named dataflow neighbour, from the traced window
(``scope_s`` of ``scopes.py``); 0 where the trace holds none."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["steps"] or "scope_s" not in trace:
        return None
    return trace["scope_s"].get("unscoped", 0.0) / trace["steps"] * 1e6
