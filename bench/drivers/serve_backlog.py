"""Driver: serving from a backlog. Every request is queued when the window
opens and the queue never empties; the window counts tokens delivered."""
from bench import serving


def run(run):
    serving.run(run, open_loop=False)
