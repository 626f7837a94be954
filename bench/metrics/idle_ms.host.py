"""Device idle per traced step while the host schedules, reconciles the
stats vector and hands tokens to requests: inside ``engine.plan``,
``engine.stats`` and ``engine.emit``. Mean over chips and steps, ms."""
from bench import phases


def read(run):
    return phases.idle_ms(run, ("engine.plan", "engine.stats", "engine.emit"))
