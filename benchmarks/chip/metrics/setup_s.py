"""Process start to the first step of the window: imports, weights,
the planner, compilation (or loading from the compile cache) and one
step per composition."""


def read(run):
    return run.setup_s
