"""Mean scheduler queue wait (``RequestRecord.queue_wait``: submit to the
start of service) over the window's requests, in ms.  Program spans."""


def read(run):
    recs = [r for r in run.records if r.t_start]
    if not recs:
        return None
    return 1e3 * sum(r.queue_wait for r in recs) / len(recs)
