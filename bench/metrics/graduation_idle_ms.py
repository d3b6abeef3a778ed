"""Device-idle milliseconds inside the onboarding polls (``train.poll``
and the spans in it), per profile graduated, in the onboarding cells."""
from bench import program_trace


def read(run):
    return program_trace.graduation_idle_ms(run)
