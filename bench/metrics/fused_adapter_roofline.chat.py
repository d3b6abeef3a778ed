"""The decode step's fused-adapter kernel against its roofline (%), in the
chat cells."""
from bench import readers


def read(run):
    return readers.fused_adapter_roofline(run)
