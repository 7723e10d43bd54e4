"""Device time per simulated step of the temporal merge (``fabric/merge``:
the rate-limited queue's sort, emission and overflow), from the traced
window.

``trace.py`` files every ``fabric/`` scope other than inject, exchange and
drain as the layer ``fabric_other``.  In a cell whose program runs the
full scheme's merge on the serial schedule, at the program's defaults,
the merge is the only such scope (the pipelined schedule's
``fabric/flush`` runs in no cell), so ``fabric_other`` is the merge
there.  None where the program names no such scope.
"""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["steps"] or "fabric_other" not in trace["layer_s"]:
        return None
    return trace["layer_s"]["fabric_other"] / trace["steps"] * 1e6
