"""Mean host self time per request (``RequestRecord.host_self_s``: the
serving thread's time inside the request's pipeline spans, its waits on
the device left out) over the window's requests, in ms.  Program spans."""


def read(run):
    got = [getattr(r, "host_self_s", None) for r in run.records
           if r.t_start]
    if not got or None in got:
        return None
    return 1e3 * sum(got) / len(got)
