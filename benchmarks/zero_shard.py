"""ZeRO-1 memory / step benchmark (DESIGN.md §9, acceptance gate).

Two measurements on an 8-way ('pod', 'data') host mesh:

1. **Per-device optimizer-state bytes** at the production leaf config —
   stacked ``(2, 4096, 4096)``, rank 256, q8 error feedback — replicated
   vs ZeRO-partitioned, from *real placed arrays* (summing the shard
   bytes resident on device 0). The partitionable state (moments + EF
   payload + per-row scales) is everything but the ``r`` int32 indices per
   layer, so the reduction must be at least ``(N_dp - 1) / N_dp`` minus
   the few replicated KB of indices. Asserted.

2. **Step wall time** at a configurable (CI-sized) leaf, replicated vs
   sharded step, both through the full chain API. On a CPU host the 8
   "devices" share the same cores, so sharding cannot beat replication on
   wall clock — the number is recorded to catch gross regressions (e.g. an
   accidental per-step all-gather of the EF buffer), not as a speedup
   claim.

  PYTHONPATH=src python -m benchmarks.zero_shard [--step-dim 1024] \\
      [--out BENCH_zero_shard.json]
"""
import os

# must precede the jax import: the device count locks at first init
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def _per_device_bytes(tree, dev) -> int:
    return sum(s.data.nbytes for x in jax.tree.leaves(tree)
               for s in x.addressable_shards if s.device == dev)


def measure_state_bytes(mesh, zcfg, *, layers=2, dim=4096, rank=256) -> dict:
    from repro.optim.api import get_optimizer
    from repro.parallel import sharding as sh

    n_dp = mesh.size
    params = {"w": jnp.zeros((layers, dim, dim), jnp.float32)}
    opt = get_optimizer("dct_adamw", lr=0.01, rank=rank, zero=zcfg)
    with jax.set_mesh(mesh):
        state = opt.init(params)
        p_specs = sh.params_specs(params, mesh)
        o_specs = sh.opt_state_specs(state, params, p_specs, zero=zcfg,
                                     mesh=mesh)
        sharded = jax.device_put(state, sh.named_shardings(o_specs, mesh))

    d0 = jax.devices()[0]
    # per-leaf state only: the shared DCT basis is one-per-device by design
    # (the paper's memory win) and identical in both placements
    b_rep = _per_device_bytes(state.leaves, d0)
    b_sh = _per_device_bytes(sharded.leaves, d0)
    reduction = 1.0 - b_sh / b_rep
    target = (n_dp - 1) / n_dp
    # the r int32 indices per layer (a few KB) replicate by design; allow
    # exactly that much shortfall from the ideal (N-1)/N
    from jax.sharding import PartitionSpec as P
    idx_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x, spec in zip(
            jax.tree.leaves(state.leaves),
            jax.tree.leaves(o_specs.leaves,
                            is_leaf=lambda s: isinstance(s, P)))
        if all(ax is None for ax in spec))
    assert reduction >= target - (idx_bytes / b_rep) - 1e-6, (
        f"per-device reduction {reduction:.5f} < (N-1)/N = {target:.5f} "
        f"beyond the replicated-index allowance")
    print(f"[zero_shard] state bytes/device: replicated {b_rep / 1e6:.2f}MB"
          f" -> zero {b_sh / 1e6:.2f}MB  "
          f"(reduction {reduction:.4f}, target {target:.4f}, "
          f"replicated idx {idx_bytes / 1e3:.1f}KB)")
    return {"leaf_shape": [layers, dim, dim], "rank": rank, "n_dp": n_dp,
            "bytes_per_device_replicated": int(b_rep),
            "bytes_per_device_zero": int(b_sh),
            "replicated_index_bytes": int(idx_bytes),
            "reduction": reduction, "target_reduction": target}


def measure_step_time(mesh, zcfg, *, layers=2, dim=1024, rank=64,
                      steps=3, warmup=1) -> dict:
    from repro.optim.api import get_optimizer
    from repro.parallel import sharding as sh

    params = {"w": jnp.zeros((layers, dim, dim), jnp.float32)}
    grads = {"w": jax.random.normal(jax.random.PRNGKey(0),
                                    (layers, dim, dim), jnp.float32)}
    rows = {}
    with jax.set_mesh(mesh):
        for label, zero in (("replicated", None), ("zero1", zcfg)):
            opt = get_optimizer("dct_adamw", lr=0.01, rank=rank, fused="fft",
                                zero=zero)
            state = opt.init(params)
            if zero is not None:
                p_specs = sh.params_specs(params, mesh)
                o_specs = sh.opt_state_specs(state, params, p_specs,
                                             zero=zero, mesh=mesh)
                state = jax.device_put(state,
                                       sh.named_shardings(o_specs, mesh))
            fn = jax.jit(opt.update, donate_argnums=1)
            times = []
            for _ in range(warmup + steps):
                t0 = time.perf_counter()
                u, state = fn(grads, state, params)
                jax.block_until_ready(u)
                times.append(time.perf_counter() - t0)
            rows[label] = sum(times[warmup:]) / steps
            print(f"[zero_shard] step {label:10s} "
                  f"{rows[label] * 1e3:9.1f} ms/step "
                  f"(leaf {layers}x{dim}x{dim} r={rank}, fft)")
    return {"leaf_shape": [layers, dim, dim], "rank": rank,
            "s_per_step_replicated": rows["replicated"],
            "s_per_step_zero": rows["zero1"]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--dim", type=int, default=4096,
                    help="leaf dim for the memory measurement")
    ap.add_argument("--rank", type=int, default=256)
    ap.add_argument("--step-dim", type=int, default=1024,
                    help="leaf dim for the wall-time measurement")
    ap.add_argument("--step-rank", type=int, default=64)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", default="BENCH_zero_shard.json")
    args = ap.parse_args(argv)

    from repro.launch.mesh import make_mesh
    from repro.parallel.zero import ZeroConfig

    n = jax.device_count()
    assert n >= 2, "zero_shard bench needs >1 device (force host devices)"
    mesh = make_mesh((2, n // 2), ("pod", "data"))
    zcfg = ZeroConfig(mode="1")

    result = {
        "bench": "zero_shard",
        "backend": jax.default_backend(),
        "n_devices": n,
        "memory": measure_state_bytes(mesh, zcfg, layers=args.layers,
                                      dim=args.dim, rank=args.rank),
        "step": measure_step_time(mesh, zcfg, layers=args.layers,
                                  dim=args.step_dim, rank=args.step_rank,
                                  steps=args.steps),
    }
    if args.out:
        from benchmarks.common import write_bench_json
        write_bench_json(args.out, result)
        print(f"[zero_shard] wrote {args.out}")
    return result


if __name__ == "__main__":
    main()
