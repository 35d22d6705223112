"""Wall seconds of the window, from its start to the end of its last job,
over the jobs completed."""


def read(run):
    jobs = run["jobs"]
    return run["window_s"] / len(jobs) if jobs else None
