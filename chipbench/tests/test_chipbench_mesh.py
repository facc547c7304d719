"""The four-chip cell at test sizes on four CPU devices: the trainer's
data-parallel step (each shard's clipped sum, one psum, the noise once
after it) agrees with the reference, which splits its rows over the
same devices; and ``correct`` comes out false for each fault the cell
can have, the missing psum (``one_shard``) among them."""
import pytest

from chipbench.tests.test_chipbench_harness import run_tiny

CELL = "qwen2-7b-dpsgd-s1k-x4"


@pytest.mark.parametrize("fault", ["none", "unchanged", "half_batch",
                                   "one_shard"])
def test_four_chip_training_run(tmp_path, fault):
    code, result, err = run_tiny(tmp_path, CELL, fault, devices=4)
    assert code == 0, err[-3000:]
    assert result["device"]["count"] == 4
    assert result["correct"] is (fault == "none"), result["checks"]
