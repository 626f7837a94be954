"""Device idle per traced step while the host waits for the sampled tokens
(``device_get``): inside ``engine.wait``. Mean over chips and steps, ms."""
from bench import phases


def read(run):
    return phases.idle_ms(run, ("engine.wait",))
