"""Share of the traced window in which no operation ran on the device and
the serving thread was in the scheduler: ``pim.wait``, ``pim.pop``,
``pim.fulfill``, or inside ``pim.batch`` but outside its pipeline spans;
averaged over the chips.  Device trace, its gaps named by the program's
spans (``span_reduce.py``)."""
import span_reduce


def read(run):
    got = span_reduce.of_run(run)
    return None if got is None else got.share(got.scheduler_s)
