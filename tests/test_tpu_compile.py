"""Every Pallas kernel compiles for a described TPU v5e at qwen2-7b widths.

Interpret mode (the CPU target of the rest of the suite) ignores block
tiling, VMEM limits and dtype packing; the TPU compiler does not. These
tests hand each ``kernels/ops.py`` wrapper shapes on a described v5e
device (no chip attached) with interpret mode off, compile, and check
that the program launches the kernel as a TPU custom call.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import ops

B, S = 8, 512
D_MODEL, D_FF, N_HEADS, N_KV, HEAD_DIM = 3584, 18944, 28, 4, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    """Kernels compile for the TPU (not interpreted), and no compile is
    written to the persistent cache, which could not read it back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF = jnp.bfloat16


def test_gram_norm_triangular(one_chip):
    text = _compile(one_chip, ops.gram_norm,
                    ((B, S, D_MODEL), BF), ((B, S, D_FF), BF))
    assert "tpu_custom_call" in text


def test_direct_norm(one_chip):
    text = _compile(one_chip, ops.direct_norm,
                    ((B, S, D_MODEL), BF), ((B, S, N_KV * HEAD_DIM), BF))
    assert "tpu_custom_call" in text


def test_segmented_norm(one_chip):
    t = B * S
    text = _compile(one_chip, lambda h, z, s: ops.segmented_norm(h, z, s, B),
                    ((t, D_MODEL), BF), ((t, D_FF), BF), ((t,), jnp.int32))
    assert "tpu_custom_call" in text


def test_clip_scale(one_chip):
    text = _compile(one_chip, ops.clip_scale,
                    ((B, S, D_FF), BF), ((B,), jnp.float32))
    assert "tpu_custom_call" in text


def test_rowsumsq(one_chip):
    text = _compile(one_chip, ops.rowsumsq, ((B, S * D_MODEL), BF))
    assert "tpu_custom_call" in text


def _qkv():
    return (((B, N_HEADS, S, HEAD_DIM), BF), ((B, N_KV, S, HEAD_DIM), BF),
            ((B, N_KV, S, HEAD_DIM), BF))


def test_flash_attention_forward(one_chip):
    text = _compile(one_chip, lambda q, k, v: ops.flash_attention_vjp(
        q, k, v, HEAD_DIM ** -0.5), *_qkv())
    assert "tpu_custom_call" in text


def test_flash_attention_backward(one_chip):
    """The dq and dkv kernels: two custom calls."""
    bq = min(256, S)

    def bwd(q, k, v, o, lse, do):
        return fa.flash_attention_bwd(q, k, v, o, lse, do,
                                      scale=HEAD_DIM ** -0.5, block_q=bq,
                                      block_k=bq, interpret=False)

    q, k, v = _qkv()
    text = _compile(one_chip, bwd, q, k, v, q,
                    ((B, N_HEADS, S, 1), jnp.float32), q)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
