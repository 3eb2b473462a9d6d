"""setup_s: seconds from the process's start to the opening of the measured
window: JAX start-up, data generation, the build, opening the engine and
warming the cell's buckets (compiling them in a run with a cold cache)."""


def read(run):
    return run.setup_s
