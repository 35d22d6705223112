"""``timings["init_s"]`` of ``dpartition(timing=True)``: host seconds
between the program's phase syncs, mean over the traced window's jobs."""


def read(run):
    vals = [job["timings"]["init_s"] for job in run["jobs"]
            if job.get("timings") and "init_s" in job["timings"]]
    return sum(vals) / len(vals) if vals else None
