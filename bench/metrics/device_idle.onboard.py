"""Share of the traced window with no device operation running, in the
onboarding cells (%)."""
from bench import readers


def read(run):
    return readers.device_idle(run)
