"""Requests per engine batch, from the engine's own counters (``served``
and ``batches`` of ``CnnServingEngine.stats()``) over the window and its
drain."""


def read(ctx):
    eng = ctx["engine_window"]
    return eng["served"] / eng["batches"] if eng["batches"] else None
