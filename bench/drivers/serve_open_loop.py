"""Driver: open-loop serving. Requests are submitted when due, whatever the
engine is doing; latency runs from the due time."""
from bench import serving


def run(run):
    serving.run(run, open_loop=True)
