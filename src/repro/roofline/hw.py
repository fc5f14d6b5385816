"""Hardware arch table for roofline analysis (brief: ROOFLINE ANALYSIS).

The seed shipped TPU v5e constants hardcoded at module level, which made
every roofline prediction (and now the tune/ autotuner's block-grid
pruning) silently wrong on any other target. The constants live in an
arch table instead: ``get_arch("v5p")`` / ``set_arch("a100")`` /
``REPRO_ARCH=a100`` select the spec; with none of those, a process on a
TPU takes the arch of its ``device_kind`` (an unknown TPU kind is an
error, never a default) and any other backend plans against v5e. The
legacy module-level names (``PEAK_FLOPS_BF16`` etc.) remain as the **v5e
defaults** for call sites that predate the table.

``cpu-est`` is a deliberately rough order-of-magnitude stand-in for the
CI container (AVX-class core, DDR bandwidth): good enough to classify a
kernel as compute- vs memory-bound, not a performance model.
"""
from __future__ import annotations

import dataclasses
import os


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    """Per-device hardware envelope used by the roofline terms."""

    name: str
    peak_flops: float        # dense-matmul peak, FLOP/s (bf16 on TPUs)
    hbm_bw: float            # bytes/s main-memory bandwidth
    ici_bw: float            # bytes/s per interconnect link
    hbm_bytes: int           # device memory capacity
    vmem_bytes: int          # fast on-chip memory a kernel can tile into
    int8_flops: float = 0.0  # int8 matmul peak (0 = no native int8 path)

    @property
    def ridge_intensity(self) -> float:
        """FLOP/byte at which compute and memory terms balance."""
        return self.peak_flops / self.hbm_bw


ARCHS: dict[str, ArchSpec] = {
    # TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, 16 GiB, ~128 MB/chip VMEM
    # budget is per-core ~16 MB usable for kernel tiles
    "v5e": ArchSpec(name="v5e", peak_flops=197e12, hbm_bw=819e9,
                    ici_bw=50e9, hbm_bytes=16 * 1024**3,
                    vmem_bytes=16 * 1024**2, int8_flops=394e12),
    # TPU v5p: 459 TFLOP/s bf16, 2765 GB/s HBM, 95 GiB
    "v5p": ArchSpec(name="v5p", peak_flops=459e12, hbm_bw=2765e9,
                    ici_bw=100e9, hbm_bytes=95 * 1024**3,
                    vmem_bytes=16 * 1024**2, int8_flops=918e12),
    # A100-80GB: 312 TFLOP/s bf16 tensor core, 2039 GB/s, NVLink 300 GB/s;
    # "vmem" maps to the combined L2 slice a persistent tile can hold
    "a100": ArchSpec(name="a100", peak_flops=312e12, hbm_bw=2039e9,
                     ici_bw=300e9, hbm_bytes=80 * 1024**3,
                     vmem_bytes=40 * 1024**2, int8_flops=624e12),
    # CI-container estimate: one AVX-512 core ~100 GFLOP/s, DDR ~20 GB/s.
    # Order-of-magnitude only — used so interpret-mode tuning runs still
    # prune with a finite ridge instead of v5e's.
    "cpu-est": ArchSpec(name="cpu-est", peak_flops=100e9, hbm_bw=20e9,
                        ici_bw=10e9, hbm_bytes=16 * 1024**3,
                        vmem_bytes=32 * 1024**2, int8_flops=200e9),
}

#: ``device_kind`` strings JAX reports for TPU chips -> arch table entry
TPU_DEVICE_KINDS = {
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
}

_DEFAULT_ARCH = "v5e"
_ACTIVE: str | None = None


def arch_names() -> tuple[str, ...]:
    return tuple(ARCHS)


def arch_for_device_kind(kind: str) -> str:
    """Arch name of a TPU ``device_kind`` (e.g. "TPU v5 lite" -> "v5e");
    raises on a kind the table does not know."""
    try:
        return TPU_DEVICE_KINDS[kind]
    except KeyError:
        raise ValueError(f"unknown TPU device_kind {kind!r}; known: "
                         f"{sorted(TPU_DEVICE_KINDS)}") from None


def _device_arch() -> str:
    """The arch of this process's TPU; v5e on any other backend."""
    import jax

    if jax.default_backend() != "tpu":
        return _DEFAULT_ARCH
    return arch_for_device_kind(jax.devices()[0].device_kind)


def get_arch(name: str | None = None) -> ArchSpec:
    """Resolve an arch spec: explicit ``name`` > ``set_arch`` >
    ``REPRO_ARCH`` env > the TPU's ``device_kind`` > v5e off-TPU."""
    if name is None:
        name = _ACTIVE or os.environ.get("REPRO_ARCH") or _device_arch()
    try:
        return ARCHS[name]
    except KeyError:
        raise ValueError(f"unknown arch {name!r}; known: {arch_names()}"
                         ) from None


def set_arch(name: str) -> ArchSpec:
    """Select the process-wide arch (``--arch`` on the CLIs routes here).
    Returns the spec so call sites can chain."""
    global _ACTIVE
    spec = get_arch(name)          # validate before committing
    _ACTIVE = spec.name
    return spec


def current() -> ArchSpec:
    """The active arch spec (see :func:`get_arch` resolution order)."""
    return get_arch()


# ---------------------------------------------------------------------------
# legacy module-level constants — the seed's v5e numbers. Kept so existing
# call sites keep importing; new code should go through get_arch()/current().
# ---------------------------------------------------------------------------
PEAK_FLOPS_BF16 = ARCHS["v5e"].peak_flops
HBM_BW = ARCHS["v5e"].hbm_bw
ICI_BW = ARCHS["v5e"].ici_bw
HBM_BYTES = ARCHS["v5e"].hbm_bytes
