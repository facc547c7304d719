"""Device self time of the step program under the phase scope
``pex.norms_backward``, in ms a step (``phases.split``): the
activation backward that yields the per-example norms and clip
coefficients (dW dead), and the book's writes."""
from chipbench.phases import phase_ms


def read(run):
    return phase_ms(run, "pex.norms_backward")
