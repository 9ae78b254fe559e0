"""GEMV compute phase's share of its roofline: the least time of its runs
in the window (each run reads one row chunk of the matrix, the vector, and
writes its slice of the result, at peak bandwidth; or its operations at
peak rate, whichever is longer) over the device time of those runs.  Device
trace: the phase is the jitted ``matvec`` module; its runs are the window's
GEMV requests times their chunks."""
import kernel_costs

#: module name of the GEMV compute phase in the trace
MODULE = r"^jit_matvec$"


def read(run):
    if run.trace is None:
        return None
    secs, _ = run.trace.kernel_time(MODULE)
    f = run.facts
    reqs = sum(1 for r in run.records if r.workload == "GEMV")
    if not secs or not reqs:
        return None
    rows, cols = f["gemv_shape"]
    runs = reqs * f["n_chunks"] * f["n_banks"]
    per = -(-rows // (f["n_chunks"] * f["n_banks"]))
    least = runs * kernel_costs.least_time_s(*kernel_costs.gemv(per, cols),
                                             run.peaks)
    return kernel_costs.roofline_share(secs, least)
