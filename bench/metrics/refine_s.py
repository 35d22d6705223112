"""``timings["refine_s"]`` of ``dpartition(timing=True)``: host seconds
between the program's phase syncs, mean over the traced window's jobs."""


def read(run):
    vals = [job["timings"]["refine_s"] for job in run["jobs"]
            if job.get("timings") and "refine_s" in job["timings"]]
    return sum(vals) / len(vals) if vals else None
