"""The admission's k-sparse bank aggregate kernel against its roofline (%)."""
from bench import readers


def read(run):
    return readers.mask_aggregate_roofline(run)
