"""Mean time per decode step in the MoE layers' expert groups: from the
first expert request's submit to the last result in hand, summed over the
layers (the engine's ``StepRecord.experts_s``, around its ``pim.experts``
spans), over the window's steps, in ms.  Program spans."""


def read(run):
    steps = run.facts.get("steps")
    if not steps or any("experts_s" not in s for s in steps):
        return None
    return 1e3 * sum(s["experts_s"] for s in steps) / len(steps)
