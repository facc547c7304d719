"""Device self time of the step program's all-reduce, all-gather and
reduce-scatter instructions, in ms a step, averaged over the cell's
chips (``phases.split``, by opcode in the step's HLO text): the
data-parallel step's gradient psum."""


def read(run):
    split = run.get("phases")
    seconds = split.get("collectives") if split else None
    return 1e3 * seconds if seconds else None
