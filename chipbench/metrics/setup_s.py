"""setup_s: process start to the first token the window could serve --
weights, plan prewarm, compilation (or loading it from the cache) and
warm-up.  The ramp of traffic before the window is not in it."""


def read(run, name):
    return run.setup_s
