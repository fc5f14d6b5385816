"""Resilient training (DESIGN.md §11): in-jit anomaly guard, escalation
ladder, verified checkpoints with rollback/quarantine, chaos harness,
progress-aware supervisor, and data-pipeline error propagation."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import DataPipeline
from repro.data.synthetic import SyntheticLM
from repro.models.config import ModelConfig
from repro.optim.api import get_optimizer
from repro.train.chaos import ChaosPlan, Fault, corrupt_file
from repro.train.checkpoint import CheckpointCorruptError, CheckpointManager
from repro.train.loop import Trainer
from repro.train.resilience import (
    HALT_EXIT_CODE,
    Action,
    ResilienceConfig,
    ResilienceManager,
    TrainingHalted,
    all_finite_tree,
    scale_hyperparam,
    select_tree,
)
from repro.train.steps import init_state, make_train_step


def _tiny():
    return ModelConfig(
        name="tiny", family="dense", d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=128, vocab_size=128, schedule=((("attn",), 2),),
        param_dtype="float32", compute_dtype="float32", remat=False,
        q_chunk=32, kv_chunk=32)


# ---------------------------------------------------------------------------
# in-jit guard primitives
# ---------------------------------------------------------------------------
def test_all_finite_tree():
    good = {"a": jnp.ones((3,)), "b": {"c": jnp.zeros((2, 2))},
            "i": jnp.arange(3)}                    # int leaves ignored
    assert bool(all_finite_tree(good))
    bad = dict(good, b={"c": jnp.array([[1.0, jnp.nan], [0.0, 0.0]])})
    assert not bool(all_finite_tree(bad))
    inf = dict(good, a=jnp.array([1.0, jnp.inf, 0.0]))
    assert not bool(all_finite_tree(inf))


def test_select_tree():
    new = {"w": jnp.ones((2,)), "s": jnp.int32(5)}
    old = {"w": jnp.zeros((2,)), "s": jnp.int32(4)}
    keep = select_tree(jnp.asarray(False), new, old)
    np.testing.assert_array_equal(np.asarray(keep["w"]), [0.0, 0.0])
    assert int(keep["s"]) == 4
    take = select_tree(jnp.asarray(True), new, old)
    np.testing.assert_array_equal(np.asarray(take["w"]), [1.0, 1.0])


def test_guarded_step_refuses_nonfinite_update():
    """A NaN-poisoned batch must leave the (donated) state untouched and
    report all_finite=False; a clean batch advances as usual."""
    cfg = _tiny()
    opt = get_optimizer("dct_adamw", lr=1e-3, rank=8, lr_scale=True)
    plan = ChaosPlan([Fault(step=1, site="grads", mode="nan")],
                     log_fn=lambda s: None)
    step_fn = jax.jit(make_train_step(cfg, opt, guard=True, chaos=plan),
                      donate_argnums=0)
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, global_batch=2)
    batch_fn = plan.wrap_batch_fn(lambda s: ds.batch(jnp.int32(s)))

    state = init_state(cfg, opt, jax.random.PRNGKey(0))
    ref = jax.tree.map(np.asarray, jax.device_get(state.params))

    state, m = step_fn(state, batch_fn(0))          # clean: commits
    assert bool(m["all_finite"])
    assert int(state.step) == 1
    after_one = jax.tree.map(np.asarray, jax.device_get(state.params))
    assert any(not np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(ref), jax.tree.leaves(after_one)))

    state, m = step_fn(state, batch_fn(1))          # poisoned: refused
    assert not bool(m["all_finite"])
    assert int(state.step) == 1                     # step did not advance
    for a, b in zip(jax.tree.leaves(after_one),
                    jax.tree.leaves(jax.device_get(state.params))):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert bool(all_finite_tree(state.params))

    state, m = step_fn(state, batch_fn(2))          # recovers
    assert bool(m["all_finite"]) and int(state.step) == 2


def test_scale_hyperparam_surgery():
    opt = get_optimizer("adamw", lr=1e-2, lr_scale=True)
    params = {"w": jnp.ones((4, 4))}
    st = opt.init(params)
    st2, hits = scale_hyperparam(st, "lr_scale", 0.25)
    assert hits == 1
    # same treedef/shapes/dtypes: no retrace when fed to a compiled step
    assert jax.tree.structure(st) == jax.tree.structure(st2)
    _, hits = scale_hyperparam(st, "nonexistent", 0.5)
    assert hits == 0


# ---------------------------------------------------------------------------
# escalation ladder policy
# ---------------------------------------------------------------------------
def _mgr(**kw):
    return ResilienceManager(ResilienceConfig(**kw), log_fn=lambda s: None)


def test_ladder_skip_then_rollback_then_halt():
    m = _mgr(max_skips=2, max_rollbacks=2, lr_cut=0.5)
    assert m.observe(1, 1.0, True).kind == "ok"
    assert m.observe(2, float("nan"), False).kind == "skip"
    assert m.observe(2, float("nan"), False).kind == "skip"
    a = m.observe(2, float("nan"), False)           # skips exhausted
    assert a.kind == "rollback" and a.lr_factor == 1.0
    assert m.lr_scale == 1.0
    a = m.observe(2, float("nan"), False)
    assert a.kind == "skip"                         # counter reset post-roll
    assert m.observe(2, float("nan"), False).kind == "skip"
    a = m.observe(2, float("nan"), False)
    assert a.kind == "rollback" and a.lr_factor == 0.5
    assert m.lr_scale == 0.5                        # cumulative cut armed
    for _ in range(2):
        assert m.observe(2, float("nan"), False).kind == "skip"
    a = m.observe(2, float("nan"), False)
    assert a.kind == "halt" and m.halted
    with pytest.raises(TrainingHalted):
        raise TrainingHalted(a.reason)


def test_ladder_divergence_spike():
    m = _mgr(spike_factor=2.0, ema_warmup=3, spike_patience=2)
    for i in range(5):
        assert m.observe(i, 1.0, True).kind == "ok"
    a = m.observe(5, 10.0, True)                    # spike 1: tolerated
    assert a.kind == "ok" and "spike" in a.reason
    a = m.observe(6, 10.0, True)                    # spike 2: tolerated
    assert a.kind == "ok"
    a = m.observe(7, 10.0, True)                    # patience exhausted
    assert a.kind == "rollback" and "diverged" in a.reason
    # healthy steps reset the spike counter
    m2 = _mgr(spike_factor=2.0, ema_warmup=3, spike_patience=2)
    for i in range(5):
        m2.observe(i, 1.0, True)
    m2.observe(5, 10.0, True)
    m2.observe(6, 1.0, True)                        # recovers
    assert m2.observe(7, 10.0, True).kind == "ok"   # patience refilled


def test_ladder_heals_and_data_offset():
    m = _mgr(max_skips=0, max_rollbacks=2, heal_steps=3)
    assert m.observe(1, float("nan"), False).kind == "rollback"
    m.rolled_back(from_step=5, to_step=2)
    assert m.data_offset == 4                       # skips the bad window
    m.skipped()
    assert m.data_offset == 5
    assert m.n_rollbacks == 1
    for i in range(3):
        m.observe(10 + i, 1.0, True)
    assert m.n_rollbacks == 0                       # budget healed
    # persistence round-trip
    d = m.state_dict()
    m2 = _mgr()
    m2.load_state_dict(d)
    assert m2.data_offset == 5 and m2.lr_scale == m.lr_scale


def test_halt_dump(tmp_path):
    m = _mgr(max_skips=0, max_rollbacks=0)
    a = m.observe(3, float("nan"), False)
    assert a.kind == "halt"
    p = m.dump(str(tmp_path / "halt.json"), context={"trainer_step": 3})
    rec = json.loads(open(p).read())
    assert rec["halted"] and rec["recent_steps"][-1]["step"] == 3
    assert rec["trainer_step"] == 3


# ---------------------------------------------------------------------------
# checkpoint integrity: CRC verify, fallback, quarantine
# ---------------------------------------------------------------------------
def test_checkpoint_crc_detects_silent_corruption(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=4, log=lambda s: None)
    state = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((4,))}
    cm.save(1, state)
    cm.save(2, state)
    # rot the newest state.npz *behind* its OK marker
    corrupt_file(str(tmp_path / "step_2" / "state.npz"), mode="bitflip")
    with pytest.raises(CheckpointCorruptError):
        cm.verify(2)
    cm.verify(1)                                    # older one is fine
    # restore_latest falls back to 1 and quarantines 2
    target = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    step, restored = cm.restore_latest(target)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))
    assert not (tmp_path / "step_2").exists()
    assert (tmp_path / "step_2.corrupt").exists()
    assert cm.all_steps() == [1]


def test_checkpoint_truncation_and_manifest_shape_mismatch(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=4, log=lambda s: None)
    state = {"w": jnp.ones((16, 16))}
    cm.save(1, state)
    corrupt_file(str(tmp_path / "step_1" / "state.npz"), mode="truncate")
    with pytest.raises(CheckpointCorruptError):
        cm.verify(1)
    assert cm.latest_verified_step() is None        # nothing survives
    assert (tmp_path / "step_1.corrupt").exists()

    cm.save(2, state)
    man = json.loads(open(tmp_path / "step_2" / "manifest.json").read())
    man["leaves"]["w"]["shape"] = [8, 8]
    with open(tmp_path / "step_2" / "manifest.json", "w") as f:
        json.dump(man, f)
    with pytest.raises(CheckpointCorruptError, match="manifest says"):
        cm.verify(2)


def test_checkpoint_preformat_loads_unverified(tmp_path):
    """Checkpoints written before the integrity format (no 'leaves'
    record) still restore — backward compatible."""
    cm = CheckpointManager(str(tmp_path), log=lambda s: None)
    state = {"w": jnp.ones((4,))}
    cm.save(3, state)
    man_path = tmp_path / "step_3" / "manifest.json"
    man = json.loads(open(man_path).read())
    del man["leaves"]
    with open(man_path, "w") as f:
        json.dump(man, f)
    assert cm.latest_verified_step() == 3
    cm.restore(3, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state))


def test_async_writer_killed_midwrite(tmp_path):
    """An aborted async writer leaves only a torn .tmp behind: the latest
    published checkpoint still loads, and a restarted manager sweeps the
    orphan."""
    plan = ChaosPlan([Fault(step=2, site="checkpoint", mode="abort",
                            arg="mid_write")], log_fn=lambda s: None)
    cm = CheckpointManager(str(tmp_path), keep=3, log=lambda s: None,
                           fault_hook=plan.bind_checkpoint_dir(
                               str(tmp_path)))
    state = {"w": jnp.ones((8, 8))}
    cm.async_save(1, state)
    cm.wait()
    cm.async_save(2, state)                         # writer dies mid-write
    cm.wait()
    assert cm.latest_verified_step() == 1           # publish never happened
    assert (tmp_path / "step_2.tmp").exists()       # torn dir left behind
    # a fresh manager (restarted process) sweeps the orphan on startup
    cm2 = CheckpointManager(str(tmp_path), log=lambda s: None)
    assert not (tmp_path / "step_2.tmp").exists()
    assert cm2.latest_verified_step() == 1


def test_save_drains_pending_writer(tmp_path):
    """The sync/async save race: save() must drain the pending writer
    before writing (two writers GC'ing the same dir tear keep-k)."""
    import threading
    import time

    release = threading.Event()

    def slow_hook(stage, step):
        if stage == "pre_publish" and step == 1:
            release.wait(5.0)

    cm = CheckpointManager(str(tmp_path), keep=2, log=lambda s: None,
                           fault_hook=slow_hook)
    state = {"w": jnp.ones((4,))}
    cm.async_save(1, state)
    time.sleep(0.05)                                # writer parked pre-publish
    t = threading.Thread(target=lambda: (time.sleep(0.05), release.set()))
    t.start()
    cm.save(2, state)                               # must drain 1 first
    t.join()
    assert cm.all_steps() == [1, 2]
    for s in (1, 2):
        cm.verify(s)


# ---------------------------------------------------------------------------
# chaos plan schema
# ---------------------------------------------------------------------------
def test_chaos_plan_spec_roundtrip(tmp_path):
    spec = [{"step": [3, 4], "site": "grads", "mode": "nan"},
            {"step": 6, "site": "checkpoint", "mode": "bitflip"},
            {"step": 2, "site": "data", "mode": "delay", "arg": 0.01}]
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(spec))
    plan = ChaosPlan.load(str(p), log_fn=lambda s: None)
    assert len(plan.faults) == 4                    # step list expanded
    assert {f.step for f in plan.at("grads")} == {3, 4}
    assert plan.to_spec()[2]["mode"] == "bitflip"
    with pytest.raises(ValueError, match="unknown fault site"):
        Fault(step=1, site="nope", mode="nan")
    with pytest.raises(ValueError, match="has no mode"):
        Fault(step=1, site="grads", mode="sigkill")
    with pytest.raises(ValueError, match="stage"):
        Fault(step=1, site="checkpoint", mode="abort", arg="nope")


def test_chaos_batch_stamp_stripped_from_model():
    from repro.train.chaos import strip_chaos_key
    plan = ChaosPlan([], log_fn=lambda s: None)
    fn = plan.wrap_batch_fn(lambda s: {"tokens": jnp.zeros((2, 4))})
    b = fn(7)
    assert int(b["_chaos_step"]) == 7
    clean, cs = strip_chaos_key(b)
    assert "_chaos_step" not in clean and int(cs) == 7
    clean2, cs2 = strip_chaos_key({"tokens": jnp.zeros((2, 4))})
    assert cs2 is None


# ---------------------------------------------------------------------------
# end-to-end: NaN window + silently-corrupted checkpoint -> skip, quarantine,
# rollback to an older verified checkpoint, finish at target step
# ---------------------------------------------------------------------------
def test_chaos_e2e_rollback_past_corrupt_checkpoint(tmp_path):
    cfg = _tiny()
    opt = get_optimizer("dct_adamw", lr=1e-3, rank=8, lr_scale=True)
    plan = ChaosPlan([
        Fault(step=5, site="grads", mode="nan"),
        Fault(step=6, site="grads", mode="nan"),
        Fault(step=7, site="grads", mode="nan"),
        Fault(step=4, site="checkpoint", mode="bitflip"),
    ], log_fn=lambda s: None)
    step_fn = jax.jit(make_train_step(cfg, opt, guard=True, chaos=plan),
                      donate_argnums=0)
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, global_batch=2)
    res = ResilienceManager(ResilienceConfig(max_skips=2, max_rollbacks=3),
                            log_fn=lambda s: None)
    lines = []
    trainer = Trainer(
        train_step=step_fn,
        init_state_fn=lambda: init_state(cfg, opt, jax.random.PRNGKey(0)),
        batch_fn=plan.wrap_batch_fn(lambda s: ds.batch(jnp.int32(s))),
        ckpt_dir=str(tmp_path), ckpt_every=2, keep=4, log_every=100,
        log_fn=lines.append, resilience=res,
        ckpt_fault_hook=plan.bind_checkpoint_dir(str(tmp_path)))
    state = trainer.run(total_steps=12)

    assert int(state.step) == 12                    # reached the target
    assert bool(all_finite_tree(state.params))      # with finite params
    assert np.isfinite(float(trainer.metrics_history[-1]["loss"]))
    assert any("rollback: step 5 -> 2" in ln for ln in lines), lines
    # the bitflipped step-4 checkpoint was quarantined on the way down
    assert (tmp_path / "step_4.corrupt").exists()
    assert res.n_rollbacks == 1 and res.n_skips == 2
    # ladder state rode the manifests of post-recovery checkpoints
    cm = CheckpointManager(str(tmp_path), log=lambda s: None)
    saved = cm.manifest(cm.latest_step())["resilience"]
    assert saved["data_offset"] == res.data_offset > 0


def test_resilient_trainer_halts_on_exhausted_ladder(tmp_path):
    cfg = _tiny()
    opt = get_optimizer("dct_adamw", lr=1e-3, rank=8, lr_scale=True)
    # NaN on every batch: skips and rollbacks can never escape
    plan = ChaosPlan([Fault(step=s, site="grads", mode="nan")
                      for s in range(40)], log_fn=lambda s: None)
    step_fn = jax.jit(make_train_step(cfg, opt, guard=True, chaos=plan),
                      donate_argnums=0)
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, global_batch=2)
    res = ResilienceManager(ResilienceConfig(max_skips=1, max_rollbacks=2,
                                             lr_cut=0.5),
                            log_fn=lambda s: None)
    trainer = Trainer(
        train_step=step_fn,
        init_state_fn=lambda: init_state(cfg, opt, jax.random.PRNGKey(0)),
        batch_fn=plan.wrap_batch_fn(lambda s: ds.batch(jnp.int32(s))),
        ckpt_dir=str(tmp_path), ckpt_every=2, log_every=100,
        log_fn=lambda s: None, resilience=res)
    with pytest.raises(TrainingHalted):
        trainer.run(total_steps=10)
    assert res.lr_scale == 0.5                      # cut applied from roll 2
    rec = json.loads(open(tmp_path / "halt.json").read())
    assert rec["halted"] and rec["ladder"]["n_rollbacks"] == 3


# ---------------------------------------------------------------------------
# data pipeline error propagation
# ---------------------------------------------------------------------------
def test_pipeline_retries_transient_errors():
    calls = []

    def flaky(step):
        calls.append(step)
        if step == 1 and calls.count(1) < 3:
            raise OSError("transient storage blip")
        return {"step": step}

    p = DataPipeline(flaky, depth=2, timeout_s=5.0, retries=3,
                     retry_backoff_s=0.01)
    try:
        for s in range(3):
            assert p.get(s)["step"] == s
    finally:
        p.close()
    assert calls.count(1) == 3                      # healed on 3rd attempt


def test_pipeline_raises_persistent_error():
    def broken(step):
        if step >= 1:
            raise ValueError("bad shard")
        return {"step": step}

    p = DataPipeline(broken, depth=2, timeout_s=10.0, retries=1,
                     retry_backoff_s=0.01)
    try:
        assert p.get(0)["step"] == 0
        with pytest.raises(RuntimeError, match="failed permanently"):
            p.get(1)
    finally:
        p.close()


# ---------------------------------------------------------------------------
# supervisor: progress-aware restarts
# ---------------------------------------------------------------------------
def _child_script(tmp_path, fail_until: int, progress: bool) -> list[str]:
    """A scripted child: increments a run counter, optionally 'writes a
    checkpoint' (bumps a progress file), exits 1 until run >= fail_until."""
    script = textwrap.dedent(f"""
        import os, sys
        d = {str(tmp_path)!r}
        cp = os.path.join(d, "count")
        n = int(open(cp).read()) + 1 if os.path.exists(cp) else 1
        open(cp, "w").write(str(n))
        if {progress!r}:
            open(os.path.join(d, "progress"), "w").write(str(n))
        sys.exit(0 if n >= {fail_until} else 1)
    """)
    return [sys.executable, "-c", script]


def _progress_fn(tmp_path):
    def fn():
        p = os.path.join(str(tmp_path), "progress")
        return int(open(p).read()) if os.path.exists(p) else None
    return fn


def test_supervise_restarts_until_success(tmp_path):
    from repro.train.supervisor import supervise
    lines = []
    rc = supervise(_child_script(tmp_path, 3, progress=True),
                   max_restarts=5, backoff_s=0.01, log=lines.append,
                   progress_fn=_progress_fn(tmp_path))
    assert rc == 0
    assert open(tmp_path / "count").read() == "3"   # failed twice, then ok
    assert any("resume context" in ln for ln in lines)
    assert any("budget reset" in ln for ln in lines)


def test_supervise_budget_resets_on_progress(tmp_path):
    """With max_restarts=1 a child that fails 3 times would exhaust the
    budget — unless every attempt makes checkpoint progress."""
    from repro.train.supervisor import supervise
    rc = supervise(_child_script(tmp_path, 4, progress=True),
                   max_restarts=1, backoff_s=0.01, log=lambda s: None,
                   progress_fn=_progress_fn(tmp_path))
    assert rc == 0


def test_supervise_halts_on_crash_loop(tmp_path):
    from repro.train.supervisor import supervise
    lines = []
    rc = supervise(_child_script(tmp_path, 99, progress=False),
                   max_restarts=10, backoff_s=0.01, log=lines.append,
                   progress_fn=_progress_fn(tmp_path), crash_loop_limit=3)
    assert rc == 1
    assert open(tmp_path / "count").read() == "3"   # stopped at the limit
    assert any("crash loop" in ln for ln in lines)


def test_supervise_never_restarts_deliberate_halt(tmp_path):
    from repro.train.supervisor import supervise
    script = textwrap.dedent(f"""
        import os, sys
        d = {str(tmp_path)!r}
        cp = os.path.join(d, "count")
        n = int(open(cp).read()) + 1 if os.path.exists(cp) else 1
        open(cp, "w").write(str(n))
        sys.exit({HALT_EXIT_CODE})
    """)
    lines = []
    rc = supervise([sys.executable, "-c", script], max_restarts=5,
                   backoff_s=0.01, log=lines.append)
    assert rc == HALT_EXIT_CODE
    assert open(tmp_path / "count").read() == "1"   # exactly one attempt
    assert any("halted deliberately" in ln for ln in lines)


# ---------------------------------------------------------------------------
# guard + rollback under ZeRO-1 sharding (8 forced host devices)
# ---------------------------------------------------------------------------
_ZERO_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import contextlib, io, json, tempfile

    import numpy as np

    from repro.launch.train import build, train

    plan = [{"step": [4, 5, 6], "site": "grads", "mode": "nan"}]
    pp = os.path.join(tempfile.mkdtemp(prefix="chaos_"), "plan.json")
    with open(pp, "w") as f:
        json.dump(plan, f)

    def run(extra):
        ck = tempfile.mkdtemp(prefix="rck_")
        argv = ["--arch", "phi3-mini-3.8b", "--smoke",
                "--optimizer", "dct_adamw", "--rank", "8",
                "--steps", "8", "--seq-len", "16", "--batch", "8",
                "--ckpt-every", "3", "--ckpt-dir", ck, "--log-every", "1",
                "--resilient", "--chaos", pp] + extra
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            train(build(argv))
        out = buf.getvalue()
        assert "rollback: step 4 -> 3" in out, out
        loss = float(out.rsplit("loss ", 1)[1].split()[0])
        assert np.isfinite(loss), out
        return loss

    l_rep = run([])
    l_zero = run(["--zero", "1"])
    print(f"replicated loss {l_rep:.6f}  zero loss {l_zero:.6f}")
    assert abs(l_rep - l_zero) < 1e-4, (l_rep, l_zero)
    print("zero resilient parity OK")
""")


def test_zero_guard_rollback_parity():
    """The guard + ladder recover identically under ZeRO-1 sharding and on
    the replicated path (8 forced host devices, fresh process)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _ZERO_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "zero resilient parity OK" in proc.stdout
