"""Device time per simulated step of the program outside the ``fabric/``
and ``obs/`` scopes (ring pop, crossbar, neuron update, spike compaction,
the scan's own bookkeeping), from the traced window."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["steps"] or "network" not in trace["layer_s"]:
        return None
    return trace["layer_s"]["network"] / trace["steps"] * 1e6
