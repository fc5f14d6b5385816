"""The check that decides ``correct``, driven through a whole run on the
CPU at a tiny size (the harness's look for a chip skipped): a sound run
passes; each fault a training cell can have, planted under the timed
path, fails; the control (the reference with float8 matmul operands, in
the program's place) fails."""
import time

import pytest

from bench import check, harness
from bench.reference import dense_decoder
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


def run(root, wrapper=None, trace=False):
    return harness.run_cell(root, "tiny.t", 2**31 + 11, 0.3, trace,
                            time.time(), require_chip=False,
                            step_wrapper=wrapper)


def unchanged_state(step):
    def broken(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return broken


def half_batch(step):
    def broken(state, batch):
        half = batch["tokens"].shape[1] // 2
        return step(state, {k: v[:, :half] for k, v in batch.items()})
    return broken


def altered_token(step):
    def broken(state, batch):
        tok = batch["tokens"]
        return step(state, dict(batch, tokens=tok.at[0, 5].set(
            (tok[0, 5] + 1) % 256)))
    return broken


def test_sound_run_is_correct_and_reports_its_metrics(root):
    r = run(root)
    assert r["correct"] is True, r["check"]
    assert set(r["metrics"]) == {"train_tokens_per_s", "peak_hbm_gib",
                                 "setup_s"}
    assert list(r)[-1] == "check"
    assert r["facts"]["compiles_in_window"] == 0
    for d in r["check"].values():
        assert d["value"] <= d["limit"]


def test_traced_run_is_correct(root):
    r = run(root, trace=True)
    assert r["correct"] is True, r["check"]
    assert "mfu" in r["metrics"] and "opt_state_gib" in r["metrics"]
    # mfu counts the FLOPs of the reference module the file names
    peak = next(iter(harness.load_json(harness.PEAKS)["devices"].values()))
    work = (dense_decoder.model_flops_per_token(tiny.MODEL,
                                                tiny.JOB["seq_len"])
            * tiny.JOB["rows_per_step"] * tiny.JOB["seq_len"] * r["attempted"])
    assert r["metrics"]["mfu"]["value"] == pytest.approx(
        100 * work / (r["device"]["window_s"] * peak["bf16_flops"]))
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def one_leaf_unchanged(step):
    """An answer altered where it is produced: one leaf's update lost."""
    def broken(state, batch):
        new, metrics = step(state, batch)
        params = dict(new.params, unembed=state.params["unembed"])
        return new._replace(params=params), metrics
    return broken


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   altered_token, one_leaf_unchanged])
def test_fault_under_the_timed_path_is_caught(root, fault):
    r = run(root, fault)
    assert r["correct"] is False
    assert any(d["value"] is None or d["value"] > d["limit"]
               for d in r["check"].values())


def test_control_in_lower_precision_is_caught():
    ref = dense_decoder.Reference(tiny.MODEL, tiny.JOB).run(5, 3)
    ctrl = dense_decoder.Reference(tiny.MODEL, tiny.JOB, "fp8").run(5, 3)
    ok, detail = check.verdict(check.numbers(ctrl, ref), tiny.LIMITS)
    assert ok is False, detail
    ok, _ = check.verdict(check.numbers(ref, ref), tiny.LIMITS)
    assert ok is True


def test_missing_leaf_or_nan_fails():
    ref = {"losses": [1.0, 1.0], "true_grad_norms": {"a": 1.0, "b": 1.0},
           "grad_norms": {"a": 1.0, "b": 1.0},
           "delta_norms": {"a": 1.0, "b": 1.0}}
    prog = dict(ref, grad_norms={"a": 1.0})
    assert check.numbers(prog, ref)["grad_norm_gap"] == float("inf")
    prog = dict(ref, losses=[float("nan"), 1.0])
    assert check.numbers(prog, ref)["first_loss_gap"] == float("inf")
