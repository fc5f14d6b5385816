import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Optimizer-only dry-run: the paper's distributed claim at the HLO level.

Lowers ``optimizer.update(grads, state, params)`` alone (no fwd/bwd) for a
full-size architecture on the production mesh and reports per-device
flops/bytes/collective payloads. This isolates the cost of the paper's
subject — Trion's DCT projection + top-r selection + low-rank
Newton-Schulz vs Dion's power-iteration/QR vs (DCT-/LD-)AdamW — and checks
the headline distributed property: the update's collective payload is
low-rank (R x r), not full-size (R x C).

  PYTHONPATH=src python -m benchmarks.optimizer_dryrun [--arch qwen2.5-32b]
"""
import argparse
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def adaptive_rank_dryrun(arch: str, rank: int, *, rounds: int = 6,
                         seed: int = 0):
    """Controller dry-run (DESIGN.md §8): drive the RankAllocator over the
    full-size arch's leaf set with seeded synthetic captured-energy
    profiles, then lower dct_adamw with the resulting per-leaf overrides
    on the production mesh.

    Checks the two closed-loop claims at scale without materializing
    weights: (1) the final allocation is non-uniform (ranks actually
    reallocate), (2) the weighted rank budget — and therefore total
    optimizer-state memory — stays within the uniform-rank footprint
    (asserted on eval_shape byte counts of the real optimizer state).
    """
    import numpy as np

    from repro.configs.registry import ARCHS
    from repro.launch.mesh import make_production_mesh
    from repro.models import transformer as T
    from repro.optim.api import get_optimizer
    from repro.parallel import sharding as sh
    from repro.telemetry.controllers import (RankAllocator,
                                             RankAllocatorConfig,
                                             leaf_inventory)

    cfg = ARCHS[arch]
    params_sds = jax.eval_shape(
        partial(T.init_params, cfg, jax.random.PRNGKey(0)))
    leaves = leaf_inventory(params_sds)
    allocator = RankAllocator(
        RankAllocatorConfig(base_rank=rank, decide_every=1), leaves)

    # synthetic but deterministic per-leaf energy profiles: wide matrices
    # (attention out / mlp down) concentrate energy, square ones spread it;
    # seeded jitter stands in for batch noise. The *controller* under test
    # is real — only the plant is simulated (this is a dry run).
    rng = np.random.default_rng(seed)
    base_ce = {p: float(np.clip(0.35 + 0.6 * (1.0 - li.cols /
                                              max(li.rows, li.cols)),
                                0.05, 0.98))
               for p, li in leaves.items()}
    jitter = {p: rng.uniform(-0.08, 0.08) for p in leaves}
    for rnd in range(1, rounds + 1):
        stats = {p: {"captured_energy": float(np.clip(
            base_ce[p] + jitter[p] + rng.normal(0, 0.01), 0.01, 1.0))}
            for p in leaves}
        for _ in range(5):                    # settle the EMA
            allocator.observe(rnd, stats)
        allocator.propose(rnd)

    alloc = allocator.alloc
    uniform = {p: min(rank, li.cols) for p, li in leaves.items()}
    distinct = sorted(set(alloc.values()))
    print(f"[adaptive-rank] {arch}: {len(leaves)} lowrank leaves, "
          f"{allocator.n_decisions} decisions, distinct ranks {distinct}")
    for p in sorted(alloc):
        mark = "  " if alloc[p] == uniform[p] else ("+ " if alloc[p] >
                                                    uniform[p] else "- ")
        print(f"  {mark}{p:40s} r={alloc[p]:4d} (uniform {uniform[p]})")
    assert len(distinct) > 1, "allocation stayed uniform — controller dead"

    # memory: eval_shape the REAL optimizer state, adaptive vs uniform
    def state_bytes(overrides):
        opt = get_optimizer("dct_adamw", lr=0.01, rank=rank,
                            overrides=overrides or None)
        sds = jax.eval_shape(opt.init, params_sds)
        return sum(int(np.prod(s.shape)) * s.dtype.itemsize
                   for s in jax.tree.leaves(sds))

    b_uniform = state_bytes(None)
    b_adaptive = state_bytes(allocator.overrides())
    print(f"[adaptive-rank] opt-state bytes: uniform {b_uniform / 1e9:.3f}GB"
          f" adaptive {b_adaptive / 1e9:.3f}GB "
          f"({(b_adaptive - b_uniform) / b_uniform * 100:+.2f}%)")
    assert b_adaptive <= b_uniform, \
        "adaptive allocation exceeded the fixed-rank memory budget"

    # and the sharding layer must derive specs for the non-uniform state
    mesh = make_production_mesh()
    with jax.set_mesh(mesh):
        opt = get_optimizer("dct_adamw", lr=0.01, rank=rank,
                            overrides=allocator.overrides())
        p_specs = sh.params_specs(params_sds, mesh)
        state_sds = jax.eval_shape(opt.init, params_sds)
        sh.opt_state_specs(state_sds, params_sds, p_specs)
    print("[adaptive-rank] opt_state_specs derived for non-uniform ranks OK")
    return alloc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-32b")
    ap.add_argument("--rank", type=int, default=256)
    ap.add_argument("--optimizers", default="trion,dion,dct_adamw,adamw")
    ap.add_argument("--adaptive-rank", action="store_true",
                    help="run the rank-allocator controller dry-run instead "
                         "of the per-optimizer HLO table")
    ap.add_argument("--device-arch", default=None,
                    help="accelerator roofline table (repro.roofline.hw); "
                         "--arch is the model, this is the device")
    args = ap.parse_args(argv)

    if args.adaptive_rank:
        return adaptive_rank_dryrun(args.arch, args.rank)

    from repro.configs.registry import ARCHS
    from repro.launch.mesh import make_production_mesh
    from repro.models import transformer as T
    from repro.optim.api import get_optimizer
    from repro.parallel import sharding as sh
    from repro.roofline.analysis import analyze_compiled

    cfg = ARCHS[args.arch]
    mesh = make_production_mesh()
    rows = []
    for name in args.optimizers.split(","):
        kw = {} if name == "adamw" else {"rank": args.rank}
        opt = get_optimizer(name, lr=0.01, **kw)
        with jax.set_mesh(mesh):
            params_sds = jax.eval_shape(
                partial(T.init_params, cfg, jax.random.PRNGKey(0)))
            p_specs = sh.params_specs(params_sds, mesh)
            state_sds = jax.eval_shape(opt.init, params_sds)
            o_specs = sh.opt_state_specs(state_sds, params_sds, p_specs)

            def with_ns(tree, specs):
                return jax.tree.map(
                    lambda s, p: jax.ShapeDtypeStruct(
                        s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
                    tree, specs,
                    is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

            params_in = with_ns(params_sds, p_specs)
            grads_in = params_in
            state_in = with_ns(state_sds, o_specs)
            compiled = jax.jit(opt.update, donate_argnums=1).lower(
                grads_in, state_in, params_in).compile()
        rep = analyze_compiled(compiled, arch=args.arch, shape="opt_only",
                               mesh_name="pod1x16x16", n_devices=mesh.size,
                               model_flops_total=0.0,
                               device_arch=args.device_arch)
        coll = rep.collectives.get("_total", {"bytes": 0, "count": 0})
        print(f"{name:12s} flops/dev={rep.flops_per_device:.3e} "
              f"bytes/dev={rep.bytes_per_device:.3e} "
              f"coll={coll['bytes'] / 1e9:8.3f}GB (n={coll['count']:.0f}) "
              f"compute={rep.compute_s * 1e3:7.2f}ms "
              f"mem={rep.memory_s * 1e3:7.2f}ms "
              f"collective={rep.collective_s * 1e3:7.2f}ms")
        rows.append((name, rep))
    return rows


if __name__ == "__main__":
    main()
