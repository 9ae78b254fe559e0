"""Set-up seconds: process start to the first timed request (device
initialisation, seeded data or weights, pinning, warm-up of the cell's own
shapes from the compile cache).  Host clock."""


def read(run):
    return run.setup_s or None
