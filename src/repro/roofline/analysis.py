"""Three-term roofline from a compiled dry-run artifact.

    compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
    memory term     = HLO_bytes / HBM_bw               (per chip)
    collective term = wire_bytes / link_bw             (per chip)

cost_analysis() of an SPMD-compiled module is already the *per-device*
program, so no further division by chip count. MODEL_FLOPS = 6*N*D (dense)
or 6*N_active*D (MoE) is computed from the config and compared against the
compiled total (useful-compute ratio: catches remat/redundancy waste).
"""
from __future__ import annotations

import dataclasses
import json

from . import hw
from .hlo_parse import collective_bytes, wire_bytes


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_per_device: float
    bytes_per_device: float
    collectives: dict
    wire_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops_total: float      # 6*N*D (or 6*N_active*D), whole step
    arg_bytes: int = 0
    out_bytes: int = 0
    temp_bytes: int = 0
    alias_bytes: int = 0
    compile_s: float = 0.0
    xla_flops: float = 0.0        # raw cost_analysis (loop bodies once)
    xla_bytes: float = 0.0
    device_arch: str = "v5e"      # hw.ARCHS key the time terms were priced at

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def memory_floor_s(self) -> float:
        """Dtype-correct HBM-streaming lower bound from memory_analysis:
        every argument read once + every non-aliased output written once.
        The cost_analysis `bytes accessed` proxy is CPU-legalized (bf16
        operands get fp32 convert copies that a TPU never materializes), so
        the table reports both (EXPERIMENTS.md §Roofline notes)."""
        traffic = self.arg_bytes + max(self.out_bytes - self.alias_bytes, 0)
        return traffic / hw.get_arch(self.device_arch).hbm_bw

    @property
    def step_s(self) -> float:
        """Roofline step time: max of the three (perfect overlap bound)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / total compiled FLOPs across devices."""
        total = self.flops_per_device * self.n_devices
        return self.model_flops_total / total if total else 0.0

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline bound."""
        denom = (self.step_s * self.n_devices
                 * hw.get_arch(self.device_arch).peak_flops)
        return self.model_flops_total / denom if denom else 0.0

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(dominant=self.dominant, step_s=self.step_s,
                 useful_ratio=self.useful_ratio, mfu=self.mfu,
                 memory_floor_s=self.memory_floor_s)
        return d


def active_params(cfg) -> tuple[float, float]:
    """(N_total, N_active) parameter counts from the config (matrices only
    in the classic 6ND sense — embeddings included, as is standard)."""
    d = cfg.d_model
    per_kind = {}

    def attn_params():
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        return d * hd * (hq + 2 * hkv) + hq * hd * d

    def mla_params():
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        return (d * cfg.q_lora_rank
                + cfg.q_lora_rank * cfg.n_heads * qk
                + d * (cfg.kv_lora_rank + cfg.qk_rope_dim)
                + cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_dim
                                                    + cfg.v_head_dim)
                + cfg.n_heads * cfg.v_head_dim * d)

    def swiglu_params(f):
        return 3 * d * f

    def moe_params():
        total = cfg.n_experts * 3 * d * cfg.moe_d_ff + d * cfg.n_experts
        active = cfg.moe_top_k * 3 * d * cfg.moe_d_ff + d * cfg.n_experts
        if cfg.n_shared_experts:
            fs = cfg.shared_d_ff or cfg.moe_d_ff * cfg.n_shared_experts
            total += 3 * d * fs
            active += 3 * d * fs
        return total, active

    def mamba_params():
        din, st = cfg.mamba_d_inner, cfg.mamba_state
        return (d * 2 * din + din * (cfg.dt_rank + 2 * st)
                + cfg.dt_rank * din + din * d)

    def rwkv_params():
        return 5 * d * d + d * d + 2 * d * cfg.rwkv_decay_lora \
            + 2 * d * cfg.d_ff + d * d

    total = active = 0.0
    for pattern, repeats in cfg.schedule:
        for kind in pattern:
            if kind in ("attn", "local"):
                t = a = attn_params() + swiglu_params(cfg.d_ff)
            elif kind == "attn_moe":
                mt, ma = moe_params()
                t, a = attn_params() + mt, attn_params() + ma
            elif kind == "mla_dense":
                t = a = mla_params() + swiglu_params(cfg.d_ff)
            elif kind == "mla_moe":
                mt, ma = moe_params()
                t, a = mla_params() + mt, mla_params() + ma
            elif kind == "mamba_dense":
                t = a = mamba_params() + swiglu_params(cfg.d_ff)
            elif kind == "mamba_moe":
                mt, ma = moe_params()
                t, a = mamba_params() + mt, mamba_params() + ma
            elif kind == "rwkv":
                t = a = rwkv_params()
            elif kind == "cross":
                t = a = attn_params() + swiglu_params(cfg.d_ff)
            elif kind in ("enc", "dec"):
                t = a = attn_params() * (2 if kind == "dec" else 1) \
                    + 2 * d * cfg.d_ff
            else:
                raise ValueError(kind)
            total += t * repeats
            active += a * repeats
    if cfg.encoder_layers:
        per = attn_params() + 2 * d * cfg.d_ff
        total += per * cfg.encoder_layers
        active += per * cfg.encoder_layers
    emb = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)
    total += emb
    active += emb
    return total, active


def model_flops(cfg, shape_kind: str, seq_len: int, global_batch: int) -> float:
    """6*N_active*D for a train step; 2*N_active*D for inference forward
    (prefill); 2*N_active*B for one decode token."""
    _, n_active = active_params(cfg)
    if shape_kind == "train":
        return 6.0 * n_active * seq_len * global_batch
    if shape_kind == "prefill":
        return 2.0 * n_active * seq_len * global_batch
    return 2.0 * n_active * global_batch          # decode: one token


def analyze_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                     n_devices: int, model_flops_total: float,
                     tp_degree: int = 16, compile_s: float = 0.0,
                     device_arch: str | None = None) -> RooflineReport:

    from .hlo_cost import module_costs

    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    # primary: our trip-count-aware, dtype-correct walker (XLA's analysis
    # counts scan bodies once and the CPU backend pads bf16 with fp32
    # converts — see hlo_cost.py)
    mc = module_costs(txt)
    flops = float(mc.flops)
    byts = float(mc.bytes)
    colls = {k: {"count": v["count"], "bytes": v["bytes"]}
             for k, v in mc.collectives.items()}
    colls["_total"] = {
        "count": sum(v["count"] for v in mc.collectives.values()),
        "bytes": sum(v["bytes"] for v in mc.collectives.values())}
    wires = wire_bytes(colls, n_devices_hint=tp_degree)
    mem = compiled.memory_analysis()
    spec = hw.get_arch(device_arch)
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, n_devices=n_devices,
        flops_per_device=flops, bytes_per_device=byts,
        collectives=colls,
        wire_bytes_per_device=wires,
        compute_s=flops / spec.peak_flops,
        memory_s=byts / spec.hbm_bw,
        collective_s=wires / spec.ici_bw,
        device_arch=spec.name,
        model_flops_total=model_flops_total,
        xla_flops=float(ca.get("flops", 0.0)),
        xla_bytes=float(ca.get("bytes accessed", 0.0)),
        arg_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
        out_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
        temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
        alias_bytes=int(getattr(mem, "alias_size_in_bytes", 0)),
        compile_s=compile_s,
    )
