"""Device self time of the step program under the phase scope
``pex.forward``, in ms a step (``phases.split``): the tapped forward
(the remat forward of a backward is the backward's)."""
from chipbench.phases import phase_ms


def read(run):
    return phase_ms(run, "pex.forward")
