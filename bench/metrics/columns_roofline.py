"""The column workloads' compute phases (VA, HST), summed over the mix: the
least time of their runs in the window over the device time of those runs.
Device trace: the phases are the jitted ``local`` and ``_lambda`` modules
(their names do not tell the workloads apart); their runs are the window's
requests of each workload times their chunks, each over ``elements /
(chunks x banks)`` int32 elements."""
import kernel_costs

#: module names of the column compute phases in the trace
MODULES = r"^jit_(local|_lambda)$"


def read(run):
    if run.trace is None:
        return None
    secs, _ = run.trace.kernel_time(MODULES)
    f = run.facts
    if not secs:
        return None
    split = f["n_chunks"] * f["n_banks"]
    least = 0.0
    for r in run.records:
        n = int(f["elements"][r.workload])
        least += split * kernel_costs.least_time_s(
            *kernel_costs.column(r.workload, -(-n // split)), run.peaks)
    return kernel_costs.roofline_share(secs, least)
