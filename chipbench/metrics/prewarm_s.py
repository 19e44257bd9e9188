"""prewarm_s: host seconds of ContinuousScheduler construction -- the
planner's capture prewarm of every bucketed GEMM tiling through the plan
store -- a part of setup_s."""


def read(run, name):
    return run.prewarm_s
