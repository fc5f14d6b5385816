"""ZeRO-1 distributed-step parity (DESIGN.md §9).

Runs in a subprocess with 8 forced host devices (device count is locked at
first jax init). The partitioned step — rows of every eligible leaf's
moments/EF split over ('pod', 'data'), the fused select+project+update
running inside shard_map per shard, one (n,)-sized psum completing the
column statistic — must produce updates that match the replicated step to
fp32 rounding, with identical selected indices: the row-block
decomposition is exact arithmetic, and only the order of the reductions
differs.

Covered: stacked / odd / transposed-orientation / ineligible leaves, the
"on" (Pallas interpret) / "fft" / "off" execution modes, q8 + fp32 EF and
discard residuals, keep-branch steps (T_u > 1), telemetry parity, the
ZeRO placement specs (per-device byte reduction), and sharded checkpoint
save -> restore onto a *different* topology (resharding) mid-run.
"""
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile

    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.optim.api import get_optimizer
    from repro.parallel import sharding as sh
    from repro.parallel.zero import ZeroConfig
    from repro.telemetry.stats import collect
    from repro.train.checkpoint import CheckpointManager

    mesh = make_mesh((2, 4), ("pod", "data"))     # N_dp = 8 over both axes
    zcfg = ZeroConfig(mode="1")
    rng = np.random.default_rng(0)

    params = {
        "w":    jnp.zeros((3, 64, 48), jnp.float32),  # scan-stacked
        "odd":  jnp.zeros((80, 33), jnp.float32),     # odd dims, rows first
        "wide": jnp.zeros((33, 80), jnp.float32),     # transposed orientation
        "bad":  jnp.zeros((36, 20), jnp.float32),     # 36 % 8 != 0 -> fallback
        "norm": jnp.zeros((64,), jnp.float32),        # full-rank Adam route
    }

    def grads_for(t):
        r = np.random.default_rng(100 + t)
        return {k: jnp.asarray(r.standard_normal(v.shape), jnp.float32)
                for k, v in params.items()}

    # Sharded and replicated runs reduce in different orders (the row
    # blocks' partial column statistics are psum'd), so updates agree to
    # fp32 rounding, not bit for bit; the selected column indices and the
    # other int32 state must agree exactly.
    RTOL, ATOL = 1e-5, 1e-7

    def assert_close(a, b, msg):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=RTOL,
                                   atol=ATOL, err_msg=msg)

    def assert_int_state_equal(sa, sb, msg):
        la, lb = jax.tree.leaves(sa), jax.tree.leaves(sb)
        assert len(la) == len(lb), msg
        for a, b in zip(la, lb):
            if a.dtype == jnp.int32:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=msg)

    # ---- 1. matching updates: fused and unfused, every leaf shape ---------
    for fused, kw in [("off", {}), ("on", {}), ("fft", {}),
                      ("off", {"error_feedback": False}),
                      ("off", {"ef_dtype": "fp32"}),
                      ("off", {"update_interval": 2})]:
        ref = get_optimizer("dct_adamw", lr=0.01, rank=8, fused=fused, **kw)
        zo = get_optimizer("dct_adamw", lr=0.01, rank=8, fused=fused,
                           zero=zcfg, **kw)
        sr, sz = ref.init(params), zo.init(params)
        with jax.set_mesh(mesh):
            for t in range(3):
                g = grads_for(t)
                ur, sr = jax.jit(ref.update)(g, sr, params)
                uz, sz = jax.jit(zo.update)(g, sz, params)
        for k in params:
            assert_close(ur[k], uz[k], f"fused={fused} kw={kw} leaf={k}")
        assert_int_state_equal(sr, sz, f"fused={fused} kw={kw}")

    # fira residual is excluded from sharding (its psum'd phi scaling
    # would feed the update arithmetic and break bit-exactness); its
    # leaves must fall back to the replicated path
    ref = get_optimizer("fira", lr=0.01, rank=8, projector="dct")
    zo = get_optimizer("fira", lr=0.01, rank=8, projector="dct", zero=zcfg)
    sr, sz = ref.init(params), zo.init(params)
    with jax.set_mesh(mesh):
        for t in range(2):
            g = grads_for(t)
            ur, sr = jax.jit(ref.update)(g, sr, params)
            uz, sz = jax.jit(zo.update)(g, sz, params)
    for k in params:
        assert_close(ur[k], uz[k], f"fira leaf={k}")
    assert_int_state_equal(sr, sz, "fira")
    print("zero update parity OK")

    # ---- 2. telemetry parity (stats psum'd inside the shard_map) ----------
    ref = get_optimizer("dct_adamw", lr=0.01, rank=8)
    zo = get_optimizer("dct_adamw", lr=0.01, rank=8, zero=zcfg)
    g = grads_for(0)

    def run(opt, st):
        with collect() as col:
            u, st = opt.update(g, st, params)
        return u, st, col.tree()

    with jax.set_mesh(mesh):
        _, _, tel_r = jax.jit(lambda s: run(ref, s))(ref.init(params))
        _, _, tel_z = jax.jit(lambda s: run(zo, s))(zo.init(params))
    assert set(tel_r) == set(tel_z) and tel_z, sorted(tel_z)
    for path in tel_r:
        for f in tel_r[path]._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(tel_z[path], f)),
                np.asarray(getattr(tel_r[path], f)), atol=1e-5,
                err_msg=f"telemetry {path}.{f}")
    print("zero telemetry parity OK")

    # ---- 3. placement: ZeRO specs cut per-device state bytes --------------
    zo = get_optimizer("dct_adamw", lr=0.01, rank=8, zero=zcfg)
    with jax.set_mesh(mesh):
        st = zo.init(params)
        p_specs = sh.params_specs(params, mesh)
        o_specs = sh.opt_state_specs(st, params, p_specs, zero=zcfg,
                                     mesh=mesh)
        st_sh = jax.device_put(st, sh.named_shardings(o_specs, mesh))
    pl = st_sh.leaves[0]["lowrank"]["w"]
    assert pl.m.sharding.spec == P(None, ("pod", "data"), None), pl.m.sharding
    assert pl.ef.q.sharding.spec == P(None, ("pod", "data"), None)
    assert pl.proj.sharding.spec == P()      # indices replicate

    def dev_bytes(tree, dev):
        return sum(s.data.nbytes for x in jax.tree.leaves(tree)
                   for s in x.addressable_shards if s.device == dev)

    d0 = jax.devices()[0]
    b_rep, b_sh = dev_bytes(st.leaves, d0), dev_bytes(st_sh.leaves, d0)
    assert b_sh < b_rep / 4, (b_sh, b_rep)   # idx/ineligible leaves replicate
    print(f"zero placement OK ({b_rep} -> {b_sh} bytes/device)")

    # ---- 4. sharded save -> restore on a DIFFERENT topology ---------------
    with jax.set_mesh(mesh):
        for t in range(2):
            _, st_sh = jax.jit(zo.update, donate_argnums=1)(
                grads_for(t), st_sh, params)
        # replicated twin advanced identically (parity reference)
        st_rep = zo.init(params)
        for t in range(2):
            _, st_rep = jax.jit(zo.update)(grads_for(t), st_rep, params)
        # the reference's next step runs on this mesh: arrays placed under
        # one mesh cannot enter a jit under another
        ur, _ = jax.jit(zo.update)(grads_for(2), st_rep, params)

    cm = CheckpointManager(tempfile.mkdtemp(prefix="zck_"), keep=2)
    cm.save(2, st_sh)                        # gathered, mesh-agnostic
    mesh2 = make_mesh((4, 2), ("pod", "data"))
    with jax.set_mesh(mesh2):
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st_sh)
        o_specs2 = sh.opt_state_specs(target, params,
                                      sh.params_specs(params, mesh2),
                                      zero=zcfg, mesh=mesh2)
        st2 = cm.restore(2, target, shardings=sh.named_shardings(o_specs2,
                                                                 mesh2))
        assert (st2.leaves[0]["lowrank"]["w"].m.sharding.spec
                == P(None, ("pod", "data"), None))
        # one more step on the new topology must still match replicated
        u2, _ = jax.jit(zo.update)(grads_for(2), st2, params)
    for k in params:
        assert_close(u2[k], ur[k], f"post-reshard leaf={k}")
    print("zero reshard restore OK")
""")


def test_zero_parity():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "zero update parity OK" in proc.stdout
    assert "zero telemetry parity OK" in proc.stdout
    assert "zero placement OK" in proc.stdout
    assert "zero reshard restore OK" in proc.stdout


# ---------------------------------------------------------------------------
# momentum-orthogonalization families (muon / trion / dion — DESIGN.md §14)
# ---------------------------------------------------------------------------
_SCRIPT_MOMENTUM = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile

    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.optim.api import get_optimizer
    from repro.parallel import sharding as sh
    from repro.parallel.zero import ZeroConfig
    from repro.telemetry.stats import collect
    from repro.train.checkpoint import CheckpointManager

    mesh = make_mesh((2, 4), ("pod", "data"))     # N_dp = 8 over both axes
    zcfg = ZeroConfig(mode="1")

    params = {
        "w":    jnp.zeros((3, 64, 48), jnp.float32),  # scan-stacked
        "odd":  jnp.zeros((80, 33), jnp.float32),     # odd dims, rows first
        "wide": jnp.zeros((33, 80), jnp.float32),     # transposed orientation
        "bad":  jnp.zeros((36, 20), jnp.float32),     # 36 % 8 != 0 -> fallback
        "norm": jnp.zeros((64,), jnp.float32),        # full-rank Adam route
    }

    def grads_for(t):
        r = np.random.default_rng(100 + t)
        return {k: jnp.asarray(r.standard_normal(v.shape), jnp.float32)
                for k, v in params.items()}

    # Sharded and replicated runs reduce in different orders (the row
    # blocks' partial column statistics are psum'd), so updates agree to
    # fp32 rounding, not bit for bit; the selected column indices and the
    # other int32 state must agree exactly.
    RTOL, ATOL = 1e-5, 1e-7

    def assert_close(a, b, msg):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=RTOL,
                                   atol=ATOL, err_msg=msg)

    def assert_int_state_equal(sa, sb, msg):
        la, lb = jax.tree.leaves(sa), jax.tree.leaves(sb)
        assert len(la) == len(lb), msg
        for a, b in zip(la, lb):
            if a.dtype == jnp.int32:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=msg)

    # ---- 1. matching updates: every family x fused off/on -----------------
    # muon both full-space (rank=None: NS on the all-gathered moment) and
    # subspace (NS on the rank-sized factor); 6 steps so momentum-driven
    # selection drift is exercised (trion's EF attracts boundary columns
    # toward ties — the gather-compute-slice scheme must stay exact)
    cases = [("muon", {}), ("muon", {"rank": 16}),
             ("trion", {"rank": 16}), ("dion", {"rank": 16})]
    for name, kw in cases:
        for fused in ("off", "on"):
            ref = get_optimizer(name, lr=0.01, fused=fused, **kw)
            zo = get_optimizer(name, lr=0.01, fused=fused, zero=zcfg, **kw)
            sr, sz = ref.init(params), zo.init(params)
            with jax.set_mesh(mesh):
                for t in range(6):
                    g = grads_for(t)
                    ur, sr = jax.jit(ref.update)(g, sr, params)
                    uz, sz = jax.jit(zo.update)(g, sz, params)
                    for k in params:
                        assert_close(ur[k], uz[k],
                                     f"{name} kw={kw} fused={fused} "
                                     f"step={t} leaf={k}")
                    assert_int_state_equal(sr, sz, f"{name} kw={kw} "
                                           f"fused={fused} step={t}")
    print("momentum zero update parity OK")

    # ---- 2. telemetry parity (subspace stats ride out of the shard_map) ---
    for name, kw in [("muon", {"rank": 16}), ("trion", {"rank": 16}),
                     ("dion", {"rank": 16})]:
        ref = get_optimizer(name, lr=0.01, **kw)
        zo = get_optimizer(name, lr=0.01, zero=zcfg, **kw)
        g = grads_for(0)

        def run(opt, st):
            with collect() as col:
                u, st = opt.update(g, st, params)
            return u, st, col.tree()

        with jax.set_mesh(mesh):
            _, _, tel_r = jax.jit(lambda s: run(ref, s))(ref.init(params))
            _, _, tel_z = jax.jit(lambda s: run(zo, s))(zo.init(params))
        assert set(tel_r) == set(tel_z) and tel_z, (name, sorted(tel_z))
        for path in tel_r:
            for f in tel_r[path]._fields:
                np.testing.assert_allclose(
                    np.asarray(getattr(tel_z[path], f)),
                    np.asarray(getattr(tel_r[path], f)), atol=1e-5,
                    err_msg=f"{name} telemetry {path}.{f}")
    print("momentum zero telemetry parity OK")

    # ---- 3. placement: oriented momentum row-shards; dion q replicates ----
    for name, kw in [("muon", {"rank": 16}), ("trion", {"rank": 16}),
                     ("dion", {"rank": 16})]:
        zo = get_optimizer(name, lr=0.01, zero=zcfg, **kw)
        with jax.set_mesh(mesh):
            st = zo.init(params)
            p_specs = sh.params_specs(params, mesh)
            o_specs = sh.opt_state_specs(st, params, p_specs, zero=zcfg,
                                         mesh=mesh)
            st_sh = jax.device_put(st, sh.named_shardings(o_specs, mesh))
        for leafname in ("w", "odd", "wide"):
            pl = st_sh.leaves[0]["lowrank"][leafname]
            lead = (None,) * (pl.m.ndim - 2)
            assert pl.m.sharding.spec == P(*lead, ("pod", "data"), None), (
                name, leafname, pl.m.sharding.spec)
            if hasattr(pl, "q"):
                assert pl.q.sharding.spec == P(), (name, leafname,
                                                   pl.q.sharding.spec)
        # ineligible leaf (36 % 8 != 0) mirrors the param placement
        bad = st_sh.leaves[0]["lowrank"]["bad"]
        assert bad.m.sharding.spec == p_specs["bad"], bad.m.sharding.spec

        def dev_bytes(tree, dev):
            return sum(s.data.nbytes for x in jax.tree.leaves(tree)
                       for s in x.addressable_shards if s.device == dev)

        d0 = jax.devices()[0]
        b_rep, b_sh = dev_bytes(st.leaves, d0), dev_bytes(st_sh.leaves, d0)
        assert b_sh < b_rep / 2, (name, b_sh, b_rep)
    print("momentum zero placement OK")

    # ---- 4. sharded save -> restore on a DIFFERENT topology ---------------
    zo = get_optimizer("trion", lr=0.01, rank=16, zero=zcfg)
    with jax.set_mesh(mesh):
        st = zo.init(params)
        p_specs = sh.params_specs(params, mesh)
        o_specs = sh.opt_state_specs(st, params, p_specs, zero=zcfg,
                                     mesh=mesh)
        st_sh = jax.device_put(st, sh.named_shardings(o_specs, mesh))
        for t in range(2):
            _, st_sh = jax.jit(zo.update, donate_argnums=1)(
                grads_for(t), st_sh, params)
        st_rep = zo.init(params)
        for t in range(2):
            _, st_rep = jax.jit(zo.update)(grads_for(t), st_rep, params)
        # the reference's next step runs on this mesh: arrays placed under
        # one mesh cannot enter a jit under another
        ur, _ = jax.jit(zo.update)(grads_for(2), st_rep, params)

    cm = CheckpointManager(tempfile.mkdtemp(prefix="zckm_"), keep=2)
    cm.save(2, st_sh)                        # gathered, mesh-agnostic
    mesh2 = make_mesh((4, 2), ("pod", "data"))
    with jax.set_mesh(mesh2):
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st_sh)
        o_specs2 = sh.opt_state_specs(target, params,
                                      sh.params_specs(params, mesh2),
                                      zero=zcfg, mesh=mesh2)
        st2 = cm.restore(2, target, shardings=sh.named_shardings(o_specs2,
                                                                 mesh2))
        u2, _ = jax.jit(zo.update)(grads_for(2), st2, params)
    for k in params:
        assert_close(u2[k], ur[k], f"post-reshard leaf={k}")
    print("momentum zero reshard restore OK")
""")


def test_zero_parity_momentum_families():
    """muon/trion/dion sharded updates match replicated to fp32 rounding
    with identical selected indices (fused off and on, stacked/odd/transposed leaves), telemetry parity,
    placement specs, and reshard-then-step (DESIGN.md §14)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT_MOMENTUM], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "momentum zero update parity OK" in proc.stdout
    assert "momentum zero telemetry parity OK" in proc.stdout
    assert "momentum zero placement OK" in proc.stdout
    assert "momentum zero reshard restore OK" in proc.stdout


def test_zero_shardable_gate():
    """Only index-based projectors shard, and the fira residual is
    excluded (its phi scaling would feed psum'd norms into the update)."""
    from repro.optim.projected_adam import ProjectedAdamRule

    assert ProjectedAdamRule(projector="dct").zero_shardable
    assert ProjectedAdamRule(projector="randperm",
                             needs_shared_basis=False).zero_shardable
    assert not ProjectedAdamRule(projector="svd",
                                 needs_shared_basis=False).zero_shardable
    assert not ProjectedAdamRule(projector="power",
                                 needs_shared_basis=False).zero_shardable
    assert not ProjectedAdamRule(projector="dct",
                                 residual="fira").zero_shardable

    # momentum-orthogonalization families (DESIGN.md §14): all shardable —
    # muon via psum'd ranking + rank-sized NS gather, trion/dion via full
    # gather-compute-slice
    from repro.optim.dion import DionRule
    from repro.optim.muon import MuonRule
    from repro.optim.trion import TrionRule

    assert MuonRule().zero_shardable
    assert MuonRule(rank=16).zero_shardable
    assert TrionRule(rank=16).zero_shardable
    assert DionRule(rank=16).zero_shardable


def test_zero_cli_gate():
    """--zero with a non-shardable optimizer must fail LOUDLY, not silently
    keep every leaf replicated (the PR-9 regression: the old gate only
    allowed dct_adamw and no-op'd everything else)."""
    import pytest

    from repro.launch.train import build, train

    base = ["--arch", "phi3-mini-3.8b", "--smoke", "--steps", "1",
            "--seq-len", "8", "--batch", "4", "--zero", "1"]
    # ldadamw's power-iteration projector state is not row-decomposable
    with pytest.raises(SystemExit, match="would silently stay replicated"):
        train(build(base + ["--optimizer", "ldadamw"]))
    # galore/frugal only shard with an index-based predefined basis
    with pytest.raises(SystemExit, match="would silently stay replicated"):
        train(build(base + ["--optimizer", "galore"]))
    # muon/trion/dion pass the shardable gate — proven by tripping the
    # NEXT gate (adaptive composition) instead of the shardable one
    for name in ("muon", "trion", "dion"):
        with pytest.raises(SystemExit, match="cannot be combined"):
            train(build(base + ["--optimizer", name,
                               "--adaptive-rank"]))


def test_zero_config_validation():
    from repro.parallel.zero import ZERO_OFF, ZeroConfig, parse_zero

    assert not ZERO_OFF.active
    assert parse_zero("1").active
    assert ZeroConfig(mode="1", axes=["data"]).axes == ("data",)
    try:
        ZeroConfig(mode="2")
    except ValueError as e:
        assert "zero mode" in str(e)
    else:
        raise AssertionError("mode '2' accepted")


def test_zero_inactive_without_mesh():
    """No mesh active -> resolve() is None and the optimizer runs the
    plain replicated path (same numbers as a zero=None build)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.optim.api import get_optimizer
    from repro.parallel.zero import ZeroConfig, resolve

    assert resolve(ZeroConfig(mode="1")) is None
    params = {"w": jnp.zeros((24, 16), jnp.float32)}
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((24, 16)),
                          jnp.float32)}
    a = get_optimizer("dct_adamw", lr=0.01, rank=4)
    b = get_optimizer("dct_adamw", lr=0.01, rank=4,
                      zero=ZeroConfig(mode="1"))
    ua, _ = jax.jit(a.update)(g, a.init(params), params)
    ub, _ = jax.jit(b.update)(g, b.init(params), params)
    np.testing.assert_array_equal(np.asarray(ua["w"]), np.asarray(ub["w"]))
