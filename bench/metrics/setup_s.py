"""Process start to the window's start: generation, compiles or cache
reads, and the warm-up jobs."""


def read(run):
    return run["setup_s"]
