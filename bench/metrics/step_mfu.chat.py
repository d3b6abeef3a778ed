"""Model FLOPs of the decode steps over their device time at peak (%), in
the chat cells."""
from bench import readers


def read(run):
    return readers.step_mfu(run)
