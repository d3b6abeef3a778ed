"""Device milliseconds per decode step, in the chat cells."""
from bench import readers


def read(run):
    return readers.decode_step_ms(run)
