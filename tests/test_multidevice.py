"""Multi-device numerics: the §Perf optimizations must not change math.

Runs in a subprocess with 8 forced host devices (device count is locked at
first jax init, so the main test process can't do this itself). Checks:
  * sp_blockwise_attention (shard_map, S over `model`) == plain blockwise
    attention under a (2, 4) mesh;
  * a full train_step gives the same loss with attn_sp on/off;
  * pure_dp and fsdp_tp layouts give the same loss.
"""
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.models.layers import blockwise_attention, sp_blockwise_attention
    from repro.models.config import ModelConfig
    from repro.models import transformer as T
    from repro.optim.api import get_optimizer
    from repro.parallel import sharding as sh
    from repro.train.steps import init_state, make_train_step

    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)

    # ---- 1. SP attention numerics ----------------------------------------
    b, s, hq, hkv, hd = 2, 64, 6, 3, 16      # heads don't divide model=4
    q = jnp.asarray(rng.standard_normal((b, s, hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), jnp.float32)
    with jax.set_mesh(mesh):
        ref = jax.jit(lambda q, k, v: blockwise_attention(
            q, k, v, causal=True, q_chunk=16, kv_chunk=16))(q, k, v)
        out = jax.jit(lambda q, k, v: sp_blockwise_attention(
            q, k, v, causal=True, q_chunk=16, kv_chunk=16))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
    print("sp-attention parity OK")

    # ---- 2. train_step loss parity: attn_sp on/off ------------------------
    cfg = ModelConfig(
        name="tiny", family="dense", d_model=32, n_heads=4, n_kv_heads=2,
        d_ff=64, vocab_size=64, schedule=((("attn",), 2),),
        param_dtype="float32", compute_dtype="float32", remat=False,
        q_chunk=16, kv_chunk=16)
    opt = get_optimizer("trion", lr=1e-3, rank=8)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, 64, (8, 64)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, 64, (8, 64)), jnp.int32),
    }
    losses = {}
    for sp in (False, True):
        c = dataclasses.replace(cfg, attn_sp=sp)
        with jax.set_mesh(mesh):
            state = init_state(c, opt, jax.random.PRNGKey(0))
            _, m = jax.jit(make_train_step(c, opt))(state, batch)
            losses[sp] = float(m["loss"])
    assert abs(losses[True] - losses[False]) < 1e-4, losses
    print("attn_sp loss parity OK", losses)

    # ---- 3. layout policy loss parity -------------------------------------
    vals = {}
    for layout in ("fsdp_tp", "pure_dp"):
        with sh.use_policy(layout=layout), jax.set_mesh(mesh):
            state = init_state(cfg, opt, jax.random.PRNGKey(0))
            _, m = jax.jit(make_train_step(cfg, opt))(state, batch)
            vals[layout] = float(m["loss"])
    assert sh.layout_policy() == "fsdp_tp"   # scoped policy restored
    assert abs(vals["pure_dp"] - vals["fsdp_tp"]) < 1e-4, vals
    print("layout loss parity OK", vals)

    # ---- 4. decode_tp logits parity (incl. MoE f-sliced experts) ----------
    moe_cfg = ModelConfig(
        name="tinymoe", family="moe", d_model=32, n_heads=4, n_kv_heads=2,
        d_ff=64, vocab_size=64, schedule=((("attn", "attn_moe"), 2),),
        n_experts=4, moe_top_k=2, moe_d_ff=16, capacity_factor=8.0,
        param_dtype="float32", compute_dtype="float32", remat=False,
        q_chunk=16, kv_chunk=16)
    params = T.init_params(moe_cfg, jax.random.PRNGKey(3))
    tok = jnp.asarray(rng.integers(0, 64, (4,)), jnp.int32)
    outs = {}
    for layout in ("fsdp_tp", "decode_tp"):
        with sh.use_policy(layout=layout), jax.set_mesh(mesh):
            cache = T.init_cache(moe_cfg, 4, 16)
            lg, _ = jax.jit(
                lambda p, c, t: T.decode_step(p, c, t, jnp.int32(0), moe_cfg)
            )(params, cache, tok)
            outs[layout] = np.asarray(lg)
    np.testing.assert_allclose(outs["decode_tp"], outs["fsdp_tp"],
                               atol=2e-5, rtol=1e-4)
    print("decode_tp logits parity OK")

    # ---- 5. elastic checkpoint restore across meshes ----------------------
    import tempfile
    from repro.train.checkpoint import CheckpointManager
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    state = {"w": w, "step": jnp.int32(7)}
    cm = CheckpointManager(tempfile.mkdtemp(prefix="ck_"), keep=2)
    cm.save(7, state)                      # saved mesh-agnostic
    # restore onto a DIFFERENT mesh with explicit shardings (elastic)
    mesh2 = make_mesh((4, 2), ("data", "model"))
    shardings = {"w": NamedSharding(mesh2, P("data", "model")),
                 "step": NamedSharding(mesh2, P())}
    target = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          state)
    restored = cm.restore(7, target, shardings=shardings)
    np.testing.assert_allclose(np.asarray(restored["w"]), np.asarray(w))
    assert restored["w"].sharding.spec == P("data", "model")
    print("elastic restore OK")
""")


def test_multidevice_parity():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "sp-attention parity OK" in proc.stdout
    assert "attn_sp loss parity OK" in proc.stdout
    assert "layout loss parity OK" in proc.stdout
    assert "decode_tp logits parity OK" in proc.stdout
    assert "elastic restore OK" in proc.stdout
