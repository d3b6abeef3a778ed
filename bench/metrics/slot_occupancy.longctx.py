"""Slot-steps that emitted a token, over the window, in the longctx cells
(%)."""
from bench import readers


def read(run):
    return readers.slot_occupancy(run)
