"""Device idle per traced step while the host builds the step's inputs
(step arrays, key split, host-to-device copies) and dispatches the step
program: inside ``engine.inputs`` and ``engine.launch``. Mean over chips
and steps, ms."""
from bench import phases


def read(run):
    return phases.idle_ms(run, ("engine.inputs", "engine.launch"))
