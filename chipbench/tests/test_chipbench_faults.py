"""A whole run at test sizes with the timed path broken underneath:
``correct`` comes out false for each fault a training cell can have,
and true with none."""
import pytest

from chipbench.tests.test_chipbench_harness import run_tiny


@pytest.mark.parametrize("cell,fault", [
    ("qwen2-7b-dpsgd-s1k", "none"),
    ("qwen2-7b-dpsgd-s1k", "unchanged"),
    ("qwen2-7b-dpsgd-s1k", "half_batch"),
    ("qwen2-7b-dpsgd-s1k", "half_grad"),
    ("qwen2-7b-dpsgd-s1k", "unclipped"),
    ("qwen2-7b-plain-s1k", "none"),
    ("qwen2-7b-plain-s1k", "unchanged"),
    ("qwen2-7b-plain-s1k", "half_batch"),
])
def test_training_run(tmp_path, cell, fault):
    code, result, err = run_tiny(tmp_path, cell, fault)
    assert code == 0, err[-3000:]
    assert result["correct"] is (fault == "none"), result["checks"]
