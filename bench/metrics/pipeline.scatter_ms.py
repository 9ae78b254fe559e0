"""Mean host time per request blocked in split and scatter (the
``cpu_dpu`` phase of ``RequestRecord.phases``) over the window's requests,
in ms.  Program spans."""


def read(run):
    recs = [r for r in run.records if r.t_start]
    if not recs:
        return None
    return 1e3 * sum(r.phases.cpu_dpu for r in recs) / len(recs)
