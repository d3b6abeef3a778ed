"""Host milliseconds per admission wave (``serve.admit_wave``), in the
chat cells."""
from bench import program_trace


def read(run):
    return program_trace.admit_wave_ms(run)
