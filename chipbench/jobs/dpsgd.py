"""DP-SGD fine-tuning: ``Trainer.run_step`` with Norms + Clip(C) +
Noise(σ) and AdamW, one batch a step (see ``training.py``)."""
from chipbench.training import TrainJob as Job  # noqa: F401
