"""Share of the traced window in which no operation ran on the device and
the serving thread was inside a pipeline span of the program
(``pim.split``, ``pim.scatter``, ``pim.scatter_cached``, ``pim.launch``,
``pim.device_wait``, ``pim.copy_out``, ``pim.merge``), averaged over the
chips.  Device trace, its gaps named by the program's spans
(``span_reduce.py``)."""
import span_reduce


def read(run):
    got = span_reduce.of_run(run)
    return None if got is None else got.share(got.pipeline_s)
