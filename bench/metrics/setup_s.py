"""Process start to the first request of the window: imports, weights and
images from the seed, engine and plan, warm-up and any compilation."""


def read(ctx):
    return ctx["setup_s"]
