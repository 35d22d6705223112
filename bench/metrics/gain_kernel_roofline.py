"""Share of the HBM-bound roofline that the Pallas gain kernel reaches:
the least time its bytes need at the chip's published HBM bandwidth, over
the summed device time of its calls in the trace.  Nothing is reported
where no call of the kernel is in the trace, or its shapes cannot be read."""

from bench import kernelbytes, peaks, tracereduce


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    calls = tracereduce.kernel_events(tr["events"], kernelbytes.GAIN_KERNEL)
    if not calls:
        return None
    total_bytes, total_ns = 0, 0
    for name, start, end, text in calls:
        shapes = kernelbytes.gain_call_shapes(text)
        if shapes is None:
            return None
        total_bytes += kernelbytes.gain_kernel_bytes(*shapes)
        total_ns += end - start
    if total_ns <= 0:
        return None
    bound_s = total_bytes / peaks.peak(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * bound_s / (total_ns * 1e-9)
