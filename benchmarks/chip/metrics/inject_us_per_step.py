"""Device time per simulated step under the ``fabric/inject`` scope
(route, wrap window, bucket packing), from the traced window."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["steps"] or "inject" not in trace["layer_s"]:
        return None
    return trace["layer_s"]["inject"] / trace["steps"] * 1e6
