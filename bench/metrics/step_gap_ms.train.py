"""Device milliseconds per training step spent idle between runs of the
step program: the traced window's idle time outside every program run on
the `XLA Modules` line, over the `bench.step` spans. Idle inside a run is
not counted; this is the wait on the host (bench/phases.py, which also logs
it by host span and the three longest runs with the gaps around them)."""
from bench import phases


def read(run):
    return phases.step_gap_ms(run)
