"""Mean time per decode step outside the PIM requests: routing, the latent
attention's host half, norms, residuals, the expert combine and the head
(the engine's ``StepRecord.host_s``, which holds its ``pim.route`` and
``pim.mla`` spans), over the window's steps, in ms.  Program spans."""


def read(run):
    steps = run.facts.get("steps")
    if not steps or any("route_s" not in s for s in steps):
        return None
    return 1e3 * sum(s["host_s"] for s in steps) / len(steps)
