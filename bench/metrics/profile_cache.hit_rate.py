"""Admissions served from the profile cache, over the window (%)."""
from bench import readers


def read(run):
    return readers.hit_rate(run)
