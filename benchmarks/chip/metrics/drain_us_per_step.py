"""Device time per simulated step under the ``fabric/drain`` scope
(merge queue, deposit into the delay rings), from the traced window."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["steps"] or "drain" not in trace["layer_s"]:
        return None
    return trace["layer_s"]["drain"] / trace["steps"] * 1e6
