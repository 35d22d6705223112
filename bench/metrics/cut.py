"""Edges cut (with the generator's multiplicity), as the numpy check
recomputes them from the labels, mean over the window's jobs."""


def read(run):
    cuts = [job["cut_ref"] for job in run["jobs"]]
    return sum(cuts) / len(cuts) if cuts else None
