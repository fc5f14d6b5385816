"""FLOP and byte functions of the optimizer's kernels against hand counts,
and the mfu and roofline arithmetic of the metric readers."""
import os
import types

import pytest

from bench import flops, harness
from bench.tests.tiny import BENCH

ROOT = os.path.dirname(BENCH)
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_qwen_mlp_leaf_ranking_matmul():
    # wg (5120, 27648) oriented (27648, 5120): S = G Q costs 2 m n^2
    f, b = flops.dct_project(1, 27648, 5120)
    assert f == 2 * 27648 * 5120 * 5120 == 1_449_551_462_400
    assert b == 4 * (27648 * 5120 * 2 + 5120 * 5120)
    # compute-bound at the bf16 peak: 7.36 ms
    assert flops.roofline_seconds(f, b, PEAK) == pytest.approx(f / 197e12)


def test_phi3_attention_leaf_and_dual_backprojection():
    # phi3 wq (3072, 3072), rank 256, 5 layers stacked
    f, b = flops.dct_project(5, 3072, 3072)
    assert f == 5 * 2 * 3072 ** 3
    f, b = flops.colgather_dual(5, 3072, 256, 3072)
    assert f == 5 * 4 * 3072 * 256 * 3072
    assert b == 4 * (5 * 2 * 3072 * 256 + 5 * 256 * 3072
                     + 5 * 2 * 3072 * 3072)
    # memory-bound: 4 * 5 * 2 * 3072^2 bytes written dominate
    assert flops.roofline_seconds(f, b, PEAK) == pytest.approx(b / 819e9)


def test_mfu_reader():
    # the FLOPs per token come from the context, as the harness puts them
    # there from the configuration's reference module
    ctx = types.SimpleNamespace(flops_per_token=3_635_281_920, steps=10,
                                facts={"tokens_per_step": 4096},
                                window_s=4.0, chips=1, peak=PEAK)
    want = 100 * 3_635_281_920 * 4096 * 10 / (4.0 * 197e12)
    assert harness.metric_reader(ROOT, "mfu")(ctx) == pytest.approx(want)
    ctx.window_s = 0
    assert harness.metric_reader(ROOT, "mfu")(ctx) is None


def test_roofline_reader_silent_without_calls():
    ctx = types.SimpleNamespace(trace=None, kernels={}, peak=PEAK)
    from bench import xtrace
    ctx.trace = xtrace.from_events({0: []}, [xtrace.Event(
        xtrace.WINDOW, 0, 1)])
    for name in ("dct_project_roofline", "colgather_dual_roofline"):
        assert harness.metric_reader(ROOT, name)(ctx) is None
