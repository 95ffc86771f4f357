"""Share of the requests served over the window and its drain, in %, whose
first stage the runtime had queued on the chip ahead of the previous
microbatch's last hop and then used: ``stage_ahead_used`` over ``served``
of ``CnnServingEngine.stats()``.  None where the engine does not count
it."""


def read(ctx):
    before, after = (ctx["engine_stats"][k] for k in ("before", "after"))
    served = after["served"] - before["served"]
    if "stage_ahead_used" not in after or not served:
        return None
    used = after["stage_ahead_used"] - before["stage_ahead_used"]
    return 100.0 * used / served
