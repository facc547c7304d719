"""Plain fine-tuning: ``Trainer.run_step`` with ``[Grads()]`` and AdamW,
one batch a step — the per-example layer bypassed (see
``training.py``)."""
from chipbench.training import TrainJob as Job  # noqa: F401
