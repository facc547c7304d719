"""Model FLOP/s utilisation over the traced window, in %: required
operations per token (``flops.py``) × tokens per second ÷ (chips ×
the chip's bf16 peak, ``peaks.py``)."""
from chipbench.peaks import peak


def read(run):
    trace = run["trace"]
    if trace is None or run["tokens"] == 0:
        return None
    rate = run["tokens"] / trace["window_s"]
    return 100.0 * run["flops_per_token"] * rate / (
        run["chips"] * peak(run["device_kind"]))
