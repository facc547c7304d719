"""Backward passes in the step program, as ``Trainer.run_step``'s
``pex_backwards`` counter reads it on the window's last step: 1 where
the clipped gradient comes from the norms backward's book, 2 where the
plan keeps a second backward."""


def read(run):
    return run["counters"].get("pex_backwards")
