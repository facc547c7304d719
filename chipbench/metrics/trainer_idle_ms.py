"""Device idle time charged to the program's own host spans
(``trainer.*`` of ``Trainer.run_step``: dispatch, fetching the loss,
the finite check, the norm statistics, a quarantine), in ms a step
(``phases.split``: each gap to the innermost span whose own part
overlaps it most)."""
from chipbench.phases import PROGRAM_SPANS


def read(run):
    split = run.get("phases")
    if not split:
        return None
    idle = [v for k, v in split["idle"].items()
            if k.startswith(PROGRAM_SPANS)]
    return 1e3 * sum(idle) if idle else None
