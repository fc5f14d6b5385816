"""Sanity tests for the trip-count-aware HLO cost model (roofline source)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline.hlo_cost import module_costs, parse_module
from repro.roofline.hlo_parse import collective_bytes


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_dot_flops_counted():
    n = 256
    sds = jax.ShapeDtypeStruct((n, n), jnp.float32)
    compiled = _compile(lambda a, b: a @ b, sds, sds)
    c = module_costs(compiled.as_text())
    expect = 2 * n**3
    assert 0.5 * expect <= c.flops <= 3 * expect, c.flops


def test_scan_multiplies_trip_count():
    """A scan with L iterations must cost ~L x the body (XLA's own
    cost_analysis counts the body once — the bug this model fixes)."""
    n, L = 128, 16
    sds = jax.ShapeDtypeStruct((n, n), jnp.float32)

    def fn(x):
        def body(c, _):
            return c @ x, None
        out, _ = jax.lax.scan(body, jnp.eye(n), None, length=L)
        return out

    compiled = _compile(fn, sds)
    c = module_costs(compiled.as_text())
    expect = 2 * n**3 * L
    assert 0.4 * expect <= c.flops <= 3 * expect, (c.flops, expect)
    xla = compiled.cost_analysis().get("flops", 0.0)
    # document the discrepancy this model exists to fix
    assert xla < 0.5 * expect, "XLA now counts trips; revisit hlo_cost"


def test_bytes_reasonable_for_elementwise():
    n = 1 << 20
    sds = jax.ShapeDtypeStruct((n,), jnp.float32)
    compiled = _compile(lambda a, b: a + b, sds, sds)
    c = module_costs(compiled.as_text())
    expect = 3 * 4 * n          # 2 reads + 1 write
    assert 0.5 * expect <= c.bytes <= 3 * expect, c.bytes


def test_parse_module_handles_index_comments():
    txt = """HloModule m
ENTRY %main (a: f32[4]) -> (f32[4], f32[4]) {
  %a = f32[4]{0} parameter(0)
  %b = f32[4]{0} add(%a, %a)
  ROOT %t = (f32[4]{0}, /*index=1*/f32[4]{0}) tuple(%b, %a)
}
"""
    comps = parse_module(txt)
    assert "__entry__" in comps
    ops = [i.opcode for i in comps["__entry__"]]
    assert "add" in ops and "tuple" in ops


def test_collective_parser_shapes():
    txt = ("  %ag = f32[128,256]{1,0} all-gather(%x), dimensions={0}\n"
           "  %ar = (bf16[64]{0}, bf16[64]{0}) all-reduce(%a, %b)\n")
    stats = collective_bytes(txt)
    assert stats["all-gather"]["bytes"] == 128 * 256 * 4
    assert stats["all-reduce"]["bytes"] == 2 * 64 * 2


def test_arch_from_tpu_device_kind():
    """A TPU's ``device_kind`` picks its arch; an unknown kind is an
    error, not a silent v5e default."""
    import pytest

    from repro.roofline import hw

    assert hw.arch_for_device_kind("TPU v5 lite") == "v5e"
    assert hw.get_arch(hw.arch_for_device_kind("TPU v5")).name == "v5p"
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        hw.arch_for_device_kind("TPU v9 imaginary")
