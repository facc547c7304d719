"""Device self time of the step program under the phase scope
``pex.stats``, in ms a step (``phases.split``): the per-example
statistics of each backward rule (``layout.add_*``), inside the norms
backward."""
from chipbench.phases import phase_ms


def read(run):
    return phase_ms(run, "pex.stats")
