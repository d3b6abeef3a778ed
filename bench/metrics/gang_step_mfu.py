"""The gang step's model FLOPs over its device time at peak, in the
onboarding cells (%)."""
from bench import readers


def read(run):
    return readers.gang_step_mfu(run)
