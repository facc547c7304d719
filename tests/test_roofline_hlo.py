"""Unit tests for ``roofline/hlo.py`` collective-byte parsing — the
edge cases the probe pipeline only exercises indirectly: operands whose
definitions are unresolvable (fusion parameters), variadic/fused
all-reduce forms, async start/done pairs, and zero-collective
modules."""
import pytest

from repro.roofline import hlo


def test_simple_all_reduce_counts_operand_bytes():
    text = """
HloModule m
ENTRY e {
  %a = f32[128,4] parameter(0)
  %ar = f32[128,4] all-reduce(%a), replica_groups={}
  ROOT %r = f32[128,4] add(%ar, %ar)
}
"""
    out = hlo.collective_bytes(text)
    assert out["all-reduce"] == 128 * 4 * 4
    assert out["total"] == out["all-reduce"]


def test_missing_operand_bytes_fall_back_to_result():
    """An operand defined outside the parsed scope (e.g. a fusion
    parameter) has no recorded size — the result type is the
    fallback, not a silent zero."""
    text = """
ENTRY e {
  %ar = bf16[64,32] all-reduce(%mystery.param), replica_groups={}
}
"""
    out = hlo.collective_bytes(text)
    assert out["all-reduce"] == 64 * 32 * 2


def test_variadic_all_reduce_sums_all_operands():
    """Fused/variadic all-reduce: tuple result, several operands — all
    operand buffers cross the wire."""
    text = """
ENTRY e {
  %a = f32[128] parameter(0)
  %b = bf16[256,2] parameter(1)
  %ar = (f32[128], bf16[256,2]) all-reduce(%a, %b), replica_groups={}
}
"""
    out = hlo.collective_bytes(text)
    assert out["all-reduce"] == 128 * 4 + 256 * 2 * 2


def test_variadic_fallback_uses_tuple_result_bytes():
    text = """
ENTRY e {
  %ar = (f32[128], bf16[256,2]) all-reduce(%p0, %p1), replica_groups={}
}
"""
    out = hlo.collective_bytes(text)
    assert out["all-reduce"] == 128 * 4 + 256 * 2 * 2


def test_async_start_variant_is_recognized():
    """``all-reduce-start`` (async form) attributes bytes to the
    all-reduce kind; the ``-done`` op must not double-count in
    collective_counts."""
    text = """
ENTRY e {
  %a = f32[1024] parameter(0)
  %s = f32[1024] all-reduce-start(%a), replica_groups={}
  %d = f32[1024] all-reduce-done(%s)
}
"""
    counts = hlo.collective_counts(text)
    assert counts == {"all-reduce": 1}
    by = hlo.collective_bytes(text)
    assert by["all-reduce"] >= 1024 * 4


def test_every_collective_kind_is_classified():
    text = """
ENTRY e {
  %a = f32[64] parameter(0)
  %g = f32[256] all-gather(%a), dimensions={0}
  %rs = f32[16] reduce-scatter(%a), dimensions={0}
  %p = f32[64] collective-permute(%a), source_target_pairs={{0,1}}
  %t = f32[64] all-to-all(%a), dimensions={0}
}
"""
    counts = hlo.collective_counts(text)
    assert counts == {"all-gather": 1, "reduce-scatter": 1,
                      "collective-permute": 1, "all-to-all": 1}
    by = hlo.collective_bytes(text)
    for kind in counts:
        assert by[kind] == 64 * 4, kind
    assert by["total"] == 4 * 64 * 4


def test_zero_collective_module():
    text = """
ENTRY e {
  %a = f32[64] parameter(0)
  ROOT %r = f32[64] add(%a, %a)
}
"""
    assert hlo.collective_bytes(text) == {"total": 0.0}
    assert hlo.collective_counts(text) == {}


def test_unknown_dtype_and_token_operands_contribute_zero():
    text = """
ENTRY e {
  %tok = token[] parameter(0)
  %ar = token[] all-reduce(%tok), replica_groups={}
}
"""
    out = hlo.collective_bytes(text)
    assert out["all-reduce"] == 0
    assert out["total"] == 0.0


def test_compiled_cost_tolerates_list_and_absent_analyses():
    # the installed jax returns one dict; an absent analysis reads as zeros
    class _Dict:
        def cost_analysis(self):
            return {"flops": 7.0, "bytes accessed": 11.0}

    class _Empty:
        def cost_analysis(self):
            return None

    assert hlo.compiled_cost(_Dict()) == (7.0, 11.0)
    assert hlo.compiled_cost(_Empty()) == (0.0, 0.0)
