"""The decode engine's matvec phases' share of their roofline: the least
time of the window's GEMV-B and GEMV-G runs over the device time of their
jitted modules (``_bias_mv``, ``_gated_mv``).  Each request runs its
operand's row chunks; a chunk of a (rows, cols) matrix reads its rows, the
vector and writes its slice at peak bandwidth (``kernel_costs.gemv``), and
GEMV-G, which reads a gate and an up matrix, counts twice.  Shapes come
from the driver's ``matvec_shapes``, by the request's ``proj`` tag.
Device trace."""
import kernel_costs

#: module names of the GEMV-B / GEMV-G compute phases in the trace
MODULES = r"^jit__(bias|gated)_mv$"


def read(run):
    shapes = run.facts.get("matvec_shapes")
    if run.trace is None or not shapes:
        return None
    secs, _ = run.trace.kernel_time(MODULES)
    if not secs:
        return None
    split = run.facts["n_chunks"] * run.facts["n_banks"]
    least = 0.0
    for r in run.records:
        proj = (r.tags or {}).get("proj")
        if proj is None:
            continue
        rows, cols = shapes[proj]
        per = -(-rows // split)
        twice = 2 if r.workload == "GEMV-G" else 1
        least += twice * split * kernel_costs.least_time_s(
            *kernel_costs.gemv(per, cols), run.peaks)
    return kernel_costs.roofline_share(secs, least) if least else None
