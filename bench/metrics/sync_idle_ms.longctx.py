"""Device-idle milliseconds inside each decode sync (``serve.sync`` and
the spans in it), in the longctx cells."""
from bench import program_trace


def read(run):
    return program_trace.sync_idle_ms(run)
