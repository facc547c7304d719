"""Device self time of the step program under the phase scope
``optim.adamw``, in ms a step (``phases.split``): the AdamW update,
with what fuses into it."""
from chipbench.phases import phase_ms


def read(run):
    return phase_ms(run, "optim.adamw")
