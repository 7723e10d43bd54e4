"""Device time per simulated step under the ``fabric/exchange`` scope
(the flush exchange between the simulated chips), from the traced window."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["steps"] or "exchange" not in trace["layer_s"]:
        return None
    return trace["layer_s"]["exchange"] / trace["steps"] * 1e6
