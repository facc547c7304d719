"""Device self time of the step program under the phase scope
``pex.noise``, in ms a step (``phases.split``): the DP-SGD noise draw
and add, once after the gradient sum."""
from chipbench.phases import phase_ms


def read(run):
    return phase_ms(run, "pex.noise")
