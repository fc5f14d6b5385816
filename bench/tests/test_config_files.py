"""A configuration's own files define its model: the layer schedule and
the init rules of its file, the FLOP count of its reference module. The
harness names no architecture, and the two configurations in the
benchmark read exactly as they did before these rules."""
import dataclasses
import json
import math
import os

import jax
import pytest

from bench import harness, program, weights
from bench.reference import dense_decoder
from bench.tests import tiny

EXPERTS = {f"{group}/{w}": "fan_in" for group in ("experts", "shared")
           for w in ("wg", "wu", "wd")}
# rules for the leaves the common rules do not know, per registry entry
INIT = {
    "deepseek-moe-16b": EXPERTS,
    "deepseek-v3-671b": dict(EXPERTS, **{"attn/kv_norm_scale": 0.1,
                                         "attn/q_norm_scale": 0.1}),
    "jamba-1.5-large-398b": {"experts/wg": "fan_in", "experts/wu": "fan_in",
                             "experts/wd": "fan_in", "mamba/a_log": 0.1,
                             "mamba/d_skip": 0.1},
}
JAMBA = ["mamba_dense", "mamba_moe", "mamba_dense", "mamba_moe", "attn",
         "mamba_moe", "mamba_dense", "mamba_moe"]
CUTS = [
    ("deepseek-moe-16b", [[["attn"], 1], [["attn_moe"], 4]]),
    ("deepseek-moe-16b", [[["attn"], 1], [["attn_moe"], 27]]),
    ("deepseek-v3-671b", [[["mla_dense"], 1], [["mla_moe"], 4]]),
    ("deepseek-v3-671b", [[["mla_dense"], 3], [["mla_moe"], 1]]),
    ("jamba-1.5-large-398b", [[JAMBA, 1]]),
]


def load(name):
    with open(os.path.join(tiny.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def cut(registry, schedule, **model):
    return {"name": "cut", "registry": registry,
            "model": dict(model, schedule=schedule)}


@pytest.mark.parametrize("registry,schedule", CUTS)
def test_schedule_cuts_of_the_registry_are_taken(registry, schedule):
    spec = cut(registry, schedule)
    cfg = program.model_config(spec)
    assert [[list(p), r] for p, r in cfg.schedule] == schedule
    assert harness.model_of(spec)["n_layers"] == cfg.n_layers == sum(
        len(p) * r for p, r in schedule)
    # at the registry's widths, shapes only: nothing is allocated
    specs = program.param_specs(cfg)
    for path, (shape, _) in specs.items():
        std = weights.leaf_std(path, shape, INIT[registry])
        assert 0 < std < 1, path


@pytest.mark.parametrize("registry,model,why", [
    ("deepseek-moe-16b", {"schedule": [[["attn"], 1], [["mla_moe"], 1]]},
     "a pattern the registry lacks"),
    ("deepseek-moe-16b", {"schedule": [[["attn_moe"], 4]]},
     "the leading segment left out"),
    ("deepseek-moe-16b", {"schedule": [[["attn_moe"], 1], [["attn"], 1]]},
     "segments out of order"),
    ("deepseek-moe-16b", {"schedule": [[["attn"], 0], [["attn_moe"], 1]]},
     "repeats of 0"),
    ("deepseek-moe-16b", {"schedule": [[["attn"], 1], [["attn_moe"], 28]]},
     "repeats above the registry's"),
    ("jamba-1.5-large-398b", {"schedule": [[JAMBA[:4], 2]]},
     "half a period"),
    ("deepseek-moe-16b", {"n_layers": 2}, "n_layers alone on a mixed stack"),
    ("qwen2.5-32b", {"n_layers": 65}, "more layers than the source"),
    ("deepseek-moe-16b", {"n_layers": 3,
                          "schedule": [[["attn"], 1], [["attn_moe"], 1]]},
     "a stated key that disagrees"),
])
def test_schedule_refused(registry, model, why):
    with pytest.raises(ValueError):
        program.model_config({"name": "cut", "registry": registry,
                              "model": model})


@pytest.mark.parametrize("name", ["qwen2.5-32b", "phi3-mini-3.8b"])
@pytest.mark.parametrize("with_job", [False, True])
def test_existing_files_give_the_same_config(name, with_job):
    from repro.configs.registry import get_config

    spec = load(name)
    job = tiny.JOB if with_job else None
    # the config as the harness built it before schedules were read
    model = dict(spec["model"])
    if job is not None:
        model["train_microbatch"] = job["rows_per_micro"]
    n = model.pop("n_layers")
    want = dataclasses.replace(get_config(spec["registry"]),
                               schedule=((("attn",), n),), **model)
    got = program.model_config(spec, job)
    for field in dataclasses.fields(want):
        assert getattr(got, field.name) == getattr(want, field.name), \
            field.name
    assert harness.model_of(spec) == spec["model"]


@pytest.mark.parametrize("registry,schedule", CUTS[::2])
def test_a_leaf_without_a_rule_is_named(registry, schedule):
    specs = program.param_specs(program.model_config(cut(registry, schedule)))
    bare = [p for p, (s, _) in specs.items()
            if weights._common_std(p.split("/"), s) is None]
    assert bare
    for path in bare:
        with pytest.raises(ValueError, match=path):
            weights.leaf_std(path, specs[path][0])


@pytest.mark.parametrize("rule", ["wq/kernel", "kernel", "scale",
                                  "ln1/scale", "bias"])
def test_a_rule_that_would_change_a_common_leaf_raises(rule):
    specs = program.param_specs(program.model_config(load("qwen2.5-32b")))
    with pytest.raises(ValueError, match="common rule"):
        for path, (shape, _) in specs.items():
            weights.leaf_std(path, shape, {rule: 0.5})


@pytest.mark.parametrize("init,match", [
    ({"wg": "fan_in", "experts/wg": 0.1}, "all match"),
    ({"experts/wg": "fan_out"}, "fan_out"),
])
def test_a_bad_rule_raises(init, match):
    with pytest.raises(ValueError, match=match):
        weights.leaf_std("segments/1/p0/moe/experts/wg", (1, 8, 16, 4), init)


def test_one_leaf_drawn_alone_equals_its_entry_under_rules():
    specs = {"embed/kernel": ((16, 8), "float32"),
             "segments/1/p0/moe/experts/wg": ((1, 4, 8, 12), "float32"),
             "segments/1/p0/moe/experts/wd": ((1, 4, 12, 8), "bfloat16"),
             "segments/0/p0/mamba/a_log": ((1, 8, 4), "float32")}
    init = {"experts/wg": "fan_in", "experts/wd": "fan_in", "a_log": 0.3}
    key = jax.random.PRNGKey(7)
    flat = weights.draw_all(key, specs, init)
    for path, (shape, dtype) in specs.items():
        one = weights.draw_one(key, path, shape, dtype, init)
        assert (one == flat[path]).all(), path
    assert weights.leaf_std("segments/1/p0/moe/experts/wd", (1, 4, 12, 8),
                            init) == 1 / math.sqrt(12)
    assert weights.leaf_std("segments/0/p0/mamba/a_log", (1, 8, 4),
                            init) == 0.3


# d_in of each kernel, or the std itself, of the two configurations' leaves
STDS = {
    "qwen2.5-32b": {"attn/wq/kernel": 5120, "attn/wk/kernel": 5120,
                    "attn/wv/kernel": 5120, "attn/wo/kernel": 5120,
                    "mlp/wg/kernel": 5120, "mlp/wu/kernel": 5120,
                    "mlp/wd/kernel": 27648, "attn/wq/bias": 0.02,
                    "attn/wk/bias": 0.02, "attn/wv/bias": 0.02},
    "phi3-mini-3.8b": {"attn/wq/kernel": 3072, "attn/wk/kernel": 3072,
                       "attn/wv/kernel": 3072, "attn/wo/kernel": 3072,
                       "mlp/wg/kernel": 3072, "mlp/wu/kernel": 3072,
                       "mlp/wd/kernel": 8192},
}


@pytest.mark.parametrize("name", ["qwen2.5-32b", "phi3-mini-3.8b"])
def test_common_leaf_stds_unchanged(name):
    spec = load(name)
    want = {"embed/kernel": 0.02, "unembed/kernel": 0.02,
            "final_norm/scale": 0.1, "ln1/scale": 0.1, "ln2/scale": 0.1}
    for leaf, v in STDS[name].items():
        want[leaf] = v if v < 1 else 1 / math.sqrt(v)
    specs = program.param_specs(program.model_config(spec))
    assert len(specs) == len(want)
    for path, (shape, _) in specs.items():
        leaf = path.removeprefix(dense_decoder.LAYER)
        assert weights.leaf_std(path, shape, spec.get("init")) == want[leaf]


def test_model_flops_by_hand():
    q = load("qwen2.5-32b")["model"]
    layer = 5120 * 5120 * 2 + 2 * 5120 * 1024 + 3 * 5120 * 27648
    assert dense_decoder.matmul_params(q) == \
        q["n_layers"] * layer + 19008 * 5120
    per_tok = dense_decoder.model_flops_per_token(q, 4096)
    assert per_tok == 6 * dense_decoder.matmul_params(q) + 6 * 4096 * 40 * 128
    p = load("phi3-mini-3.8b")["model"]
    layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    assert dense_decoder.matmul_params(p) == 5 * layer + 32064 * 3072


@pytest.mark.parametrize("name,want", [("qwen2.5-32b", 3_635_281_920),
                                       ("phi3-mini-3.8b", 4_365_877_248)])
def test_mfu_flops_per_token_as_before(name, want):
    spec = load(name)
    count = harness.reference_module(spec).model_flops_per_token
    assert count(harness.model_of(spec), 4096) == want


def test_a_routed_expert_config_runs_its_setup_steps():
    """A tiny file against the deepseek-moe-16b entry: a dense layer, then
    an expert layer, built and driven through set-up by the program."""
    spec = {"name": "tiny-moe", "registry": "deepseek-moe-16b",
            "reference": "dense_decoder",
            "model": {"d_model": 64, "n_heads": 4, "n_kv_heads": 4,
                      "head_dim": 16, "d_ff": 128, "vocab_size": 256,
                      "n_experts": 4, "moe_top_k": 2, "n_shared_experts": 1,
                      "moe_d_ff": 32, "shared_d_ff": 32,
                      "param_dtype": "float32", "compute_dtype": "float32",
                      "schedule": [[["attn"], 1], [["attn_moe"], 1]]},
            "init": EXPERTS}
    prog = program.Program(spec, tiny.JOB, 2**31 + 7)
    run = prog.start()
    assert len(run.losses) == tiny.JOB["check_steps"]
    assert all(math.isfinite(x) for x in run.losses)
    experts = [p for p in prog.specs if "/experts/" in p]
    assert len(experts) == 3
    assert set(run.grad_norms) == set(prog.specs) == set(run.delta_norms)
    for path, g in run.grad_norms.items():
        assert math.isfinite(g) and g > 0, path
        assert math.isfinite(run.delta_norms[path]), path
    prog.release()
