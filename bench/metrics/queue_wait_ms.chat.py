"""Milliseconds an admitted request waited in the scheduler's queue, from
submit to its admission wave, in the chat cells."""
from bench import program_trace


def read(run):
    return program_trace.queue_wait_ms(run)
