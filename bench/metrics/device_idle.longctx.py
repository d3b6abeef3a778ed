"""Share of the traced window with no operation on the device (%), in the
longctx cells."""
from bench import readers


def read(run):
    return readers.device_idle(run)
