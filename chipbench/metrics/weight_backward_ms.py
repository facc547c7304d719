"""Device self time of the step program under the phase scope
``pex.weight_backward``, in ms a step (``phases.split``): every
backward that yields the gradient, and the book's contraction."""
from chipbench.phases import phase_ms


def read(run):
    return phase_ms(run, "pex.weight_backward")
