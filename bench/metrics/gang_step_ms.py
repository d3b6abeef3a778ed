"""Device time per gang step of the roster trainer, in the onboarding cells
(ms)."""
from bench import readers


def read(run):
    return readers.gang_step_ms(run)
