"""Driver: onboarding. The roster trainer runs whole sync intervals until
the window's time is up; slots graduate and refill as they reach the step
count."""
from bench import onboarding


def run(run):
    onboarding.run(run)
