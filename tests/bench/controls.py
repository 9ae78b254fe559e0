"""A cell's control, put in the program's place: every request the window
sends gets its answer from the plain reference one precision lower (the
configuration's ``*_control`` functions), at the session's door, so that
the rest of the run (window, sample, check, result line) goes on as usual
around it and decides ``correct``."""
import concurrent.futures
import contextlib
import threading

import numpy as np

import harness


class ControlSession:
    """A ``pim.session`` whose ``submit`` answers from the control; every
    other call goes to the real session."""

    def __init__(self, session, ref):
        self._session, self._ref = session, ref
        self._lock = threading.Lock()
        self._matrices: dict = {}           # id(handle) -> device copy

    def start(self):
        self._session.start()
        return self

    def submit(self, workload, *args, **_):
        fut = concurrent.futures.Future()
        try:
            fut.set_result(self._answer(workload, args))
        except Exception as e:              # the run counts it as failed
            fut.set_exception(e)
        return fut

    def _answer(self, workload, args):
        if workload == "GEMV":
            handle, x = args
            with self._lock:
                if id(handle) not in self._matrices:
                    import jax.numpy as jnp
                    self._matrices[id(handle)] = jnp.asarray(handle.value)
                a = self._matrices[id(handle)]
            return self._ref.gemv_control(a, np.asarray(x)[None])[0]
        return self._ref.column_control(workload, args)

    def __getattr__(self, name):
        return getattr(self._session, name)


@contextlib.contextmanager
def control_in_place(cell):
    """Within the block, ``pim.session`` opens a :class:`ControlSession`
    answering from ``cell``'s reference."""
    from repro import pim
    ref = harness.load_reference(cell.config["reference"])
    real = pim.session
    pim.session = lambda *a, **kw: ControlSession(real(*a, **kw), ref)
    try:
        yield
    finally:
        pim.session = real
