"""The decode step's fused-adapter kernel against its roofline (%), in the
longctx cells."""
from bench import readers


def read(run):
    return readers.fused_adapter_roofline(run)
