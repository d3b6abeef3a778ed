"""Admission's model FLOPs (prefill and aggregation) over its device time
at peak, in the chat cells (%)."""
from bench import readers


def read(run):
    return readers.admit_mfu(run)
