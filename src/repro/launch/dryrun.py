import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines, before any jax-importing module: jax locks
#   the device count at first init, and the production meshes need 512
#   placeholder host devices (brief: MULTI-POD DRY-RUN step 0).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real step function — train_step (fwd + bwd +
microbatch accumulation + the paper's optimizer), prefill, or serve_step —
against ShapeDtypeStruct inputs carrying the production NamedShardings,
compiles it, prints memory_analysis() (fits?) and cost_analysis()
(FLOPs/bytes for §Roofline), and parses the compiled HLO for collective
payloads. Results go to JSON for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k \
      [--multi-pod] [--optimizer trion] [--rank 256] [--out results.json]
  python -m repro.launch.dryrun --all --out-dir results/dryrun/
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCHS, ASSIGNED
from repro.configs.shapes import SHAPES, batch_specs, skip_reason
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim.api import get_optimizer
from repro.parallel import sharding as sh
from repro.roofline.analysis import analyze_compiled, model_flops
from repro.serve.engine import make_serve_step
from repro.train.steps import TrainState, init_state, make_train_step


def _with_ns(tree_sds, tree_specs, mesh):
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        tree_sds, tree_specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _train_lowered(cfg, mesh, optimizer_name: str, rank: int,
                   shape_name: str, accum_dtype: str):
    spec = SHAPES[shape_name]
    opt_kw = {}
    if optimizer_name == "trion" and cfg.param_dtype == "bfloat16":
        # >=90B-class archs: bf16 momentum halves optimizer HBM
        # (DESIGN.md §7; quality trade recorded in EXPERIMENTS.md)
        opt_kw["momentum_dtype"] = "bfloat16"
    opt = get_optimizer(optimizer_name, lr=0.01, rank=rank, **opt_kw)
    state_sds = jax.eval_shape(
        partial(init_state, cfg, opt, jax.random.PRNGKey(0)))
    p_specs = sh.params_specs(state_sds.params, mesh)
    o_specs = sh.opt_state_specs(state_sds.opt_state, state_sds.params,
                                 p_specs)
    state_specs = TrainState(P(), p_specs, o_specs)

    batch_sds = batch_specs(cfg, shape_name)
    b_specs = sh.batch_specs_tree(batch_sds, mesh)

    state_in = _with_ns(state_sds, state_specs, mesh)
    batch_in = _with_ns(batch_sds, b_specs, mesh)

    step = make_train_step(cfg, opt, accum_dtype=accum_dtype)
    out_ns = (jax.tree.map(lambda p: NamedSharding(mesh, p), state_specs,
                           is_leaf=lambda x: isinstance(x, P)), None)
    fn = jax.jit(step, donate_argnums=0, out_shardings=out_ns)
    return fn.lower(state_in, batch_in)


def _prefill_lowered(cfg, mesh, shape_name: str):
    spec = SHAPES[shape_name]
    params_sds = jax.eval_shape(
        partial(T.init_params, cfg, jax.random.PRNGKey(0)))
    p_specs = sh.params_specs(params_sds, mesh)
    params_in = _with_ns(params_sds, p_specs, mesh)

    batch_sds = batch_specs(cfg, shape_name, with_targets=False)
    batch_in = _with_ns(batch_sds, sh.batch_specs_tree(batch_sds, mesh),
                        mesh)

    def prefill_fn(params, batch):
        logits, cache, _ = T.prefill(params, batch, cfg,
                                     max_len=spec.seq_len)
        return logits, cache

    return jax.jit(prefill_fn).lower(params_in, batch_in)


def _decode_lowered(cfg, mesh, shape_name: str):
    spec = SHAPES[shape_name]
    b, s = spec.global_batch, spec.seq_len
    params_sds = jax.eval_shape(
        partial(T.init_params, cfg, jax.random.PRNGKey(0)))
    p_specs = sh.params_specs(params_sds, mesh)
    params_in = _with_ns(params_sds, p_specs, mesh)

    cache_sds = jax.eval_shape(partial(T.init_cache, cfg, b, s))
    c_specs = sh.cache_specs_tree(cache_sds, mesh)
    cache_in = _with_ns(cache_sds, c_specs, mesh)

    dp = sh.dp_axes(mesh) or None
    dp_n = sh._axis_size(mesh, dp)
    tok_spec = P(dp) if dp and b % dp_n == 0 else P()
    token_in = jax.ShapeDtypeStruct((b,), jnp.int32,
                                    sharding=NamedSharding(mesh, tok_spec))
    pos_in = jax.ShapeDtypeStruct((), jnp.int32,
                                  sharding=NamedSharding(mesh, P()))

    serve = make_serve_step(cfg)
    out_ns = (None, jax.tree.map(lambda p: NamedSharding(mesh, p), c_specs,
                                 is_leaf=lambda x: isinstance(x, P)))
    fn = jax.jit(serve, donate_argnums=1, out_shardings=out_ns)
    return fn.lower(params_in, cache_in, token_in, pos_in)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             optimizer: str = "trion", rank: int = 256,
             accum_dtype: str | None = None, save_hlo: str | None = None,
             sp_attn: bool = False, layout: str | None = None,
             microbatch: int | None = None, baseline: bool = False,
             device_arch: str | None = None, verbose: bool = True) -> dict:
    import dataclasses

    cfg = ARCHS[arch]
    if baseline:
        cfg = dataclasses.replace(cfg, attn_sp=False, layout="fsdp_tp",
                                  decode_layout="fsdp_tp")
    if microbatch is not None:
        cfg = dataclasses.replace(cfg, train_microbatch=microbatch)
    if sp_attn:
        # iter-1 (kept): shard_map sequence-parallel attention.
        # iter-2 (sequence-parallel residual stream) was REFUTED under the
        # FSDP x TP layout — see EXPERIMENTS.md §Perf — so seq_parallel
        # stays off (the scoped policy below pins it).
        cfg = dataclasses.replace(cfg, attn_sp=True)
    spec = SHAPES[shape_name]
    # pure_dp applies to TRAIN cells only: at 32k-sequence inference the
    # model axis must keep spreading attention work — measured regression
    # otherwise (EXPERIMENTS.md §Perf iter-5 notes). decode cells use the
    # per-arch decode layout (§Perf iter-6).
    if layout:
        eff_layout = layout
    elif spec.kind == "train":
        eff_layout = cfg.layout
    elif spec.kind == "decode":
        eff_layout = cfg.decode_layout
    else:
        eff_layout = "fsdp_tp"
    if eff_layout == "pure_dp":
        # batch shards over every axis -> no microbatch loop needed
        cfg = dataclasses.replace(cfg, train_microbatch=0)
    mesh_name = "pod2x16x16" if multi_pod else "pod1x16x16"
    reason = skip_reason(cfg, shape_name)
    if reason is not None:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skip", "reason": reason}

    if accum_dtype is None:
        # bf16-weight archs (>=27B): bf16 gradient accumulators too
        # (halves grad HBM; precision trade in DESIGN.md §7)
        accum_dtype = ("bfloat16" if cfg.param_dtype == "bfloat16"
                       else "float32")

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    # layout scoped per cell (not a process-global): concurrent run_cell
    # calls under different layouts cannot race each other's specs
    with sh.use_policy(layout=eff_layout, seq_parallel=False), \
            jax.set_mesh(mesh):
        if spec.kind == "train":
            lowered = _train_lowered(cfg, mesh, optimizer, rank, shape_name,
                                     accum_dtype)
        elif spec.kind == "prefill":
            lowered = _prefill_lowered(cfg, mesh, shape_name)
        else:
            lowered = _decode_lowered(cfg, mesh, shape_name)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
    t_total = time.perf_counter() - t0

    mf = model_flops(cfg, spec.kind, spec.seq_len, spec.global_batch)
    report = analyze_compiled(
        compiled, arch=arch, shape=shape_name, mesh_name=mesh_name,
        n_devices=mesh.size, model_flops_total=mf,
        tp_degree=mesh.shape["model"], compile_s=t_total,
        device_arch=device_arch)

    if verbose:
        print(f"== {arch} x {shape_name} x {mesh_name} ==")
        print("memory_analysis:", compiled.memory_analysis())
        ca = compiled.cost_analysis()
        print("xla cost_analysis (loop bodies once): flops=%.3e bytes=%.3e"
              % (ca.get("flops", 0), ca.get("bytes accessed", 0)))
        print("trip-aware per-device: flops=%.3e bytes=%.3e"
              % (report.flops_per_device, report.bytes_per_device))
        print("collectives:", json.dumps(report.collectives))
        print("roofline: compute=%.4fs memory=%.4fs collective=%.4fs "
              "dominant=%s mfu=%.4f useful=%.2f"
              % (report.compute_s, report.memory_s, report.collective_s,
                 report.dominant, report.mfu, report.useful_ratio))
        print(f"lower={t_lower:.1f}s compile={t_total - t_lower:.1f}s")

    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(compiled.as_text())

    rec = report.to_json()
    rec["status"] = "ok"
    rec["optimizer"] = optimizer if spec.kind == "train" else None
    rec["accum_dtype"] = accum_dtype if spec.kind == "train" else None
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all 40 assigned cells on this mesh")
    ap.add_argument("--optimizer", default="trion")
    ap.add_argument("--rank", type=int, default=256)
    ap.add_argument("--accum-dtype", default=None)
    ap.add_argument("--sp-attn", action="store_true",
                    help="force sequence-parallel attention (§Perf iter-1)")
    ap.add_argument("--layout", choices=("fsdp_tp", "pure_dp", "decode_tp"), default=None,
                    help="override the per-arch layout policy")
    ap.add_argument("--baseline", action="store_true",
                    help="strip per-arch optimizations (paper-faithful)")
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--device-arch", default=None,
                    help="accelerator roofline table to price the report "
                         "against (repro.roofline.hw: v5e/v5p/a100/"
                         "cpu-est); --arch is the *model*, this is the "
                         "*device*; default REPRO_ARCH env or v5e")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--out", default=None, help="write JSON record(s) here")
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for arch in ASSIGNED:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    records = []
    n_fail = 0
    for arch, shape in cells:
        try:
            rec = run_cell(arch, shape, multi_pod=args.multi_pod,
                           optimizer=args.optimizer, rank=args.rank,
                           accum_dtype=args.accum_dtype,
                           sp_attn=args.sp_attn, layout=args.layout,
                           microbatch=args.microbatch,
                           baseline=args.baseline,
                           device_arch=args.device_arch,
                           save_hlo=args.save_hlo)
        except Exception as e:                      # noqa: BLE001
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape,
                   "mesh": "pod2x16x16" if args.multi_pod else "pod1x16x16",
                   "status": "error", "error": f"{type(e).__name__}: {e}"}
            n_fail += 1
        records.append(rec)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(records if len(records) > 1 else records[0], f,
                      indent=1)
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
