"""``--arch <id>`` lookup for every assigned architecture (+ paper models)."""
from __future__ import annotations

from . import (
    command_r_plus_104b,
    deepseek_moe_16b,
    deepseek_v3_671b,
    gemma3_27b,
    jamba15_large_398b,
    llama32_vision_90b,
    llama_paper,
    phi3_mini_3p8b,
    qwen25_32b,
    rwkv6_1p6b,
    whisper_large_v3,
)

_MODULES = {
    "whisper-large-v3": whisper_large_v3,
    "llama-3.2-vision-90b": llama32_vision_90b,
    "deepseek-v3-671b": deepseek_v3_671b,
    "deepseek-moe-16b": deepseek_moe_16b,
    "jamba-1.5-large-398b": jamba15_large_398b,
    "rwkv6-1.6b": rwkv6_1p6b,
    "gemma3-27b": gemma3_27b,
    "qwen2.5-32b": qwen25_32b,
    "phi3-mini-3.8b": phi3_mini_3p8b,
    "command-r-plus-104b": command_r_plus_104b,
}

ARCHS = {name: mod.CONFIG for name, mod in _MODULES.items()}
SMOKES = {name: mod.SMOKE for name, mod in _MODULES.items()}

# the paper's own models, addressable the same way, each with a reduced
# same-family smoke config for CPU rehearsals
_PAPER = {
    "llama-30m": llama_paper.LLAMA_30M,
    "llama-350m": llama_paper.LLAMA_350M,
    "llama-800m": llama_paper.LLAMA_800M,
    "llama-1.3b": llama_paper.LLAMA_1_3B,
}
ARCHS.update(_PAPER)
SMOKES.update({name: cfg.reduced() for name, cfg in _PAPER.items()})

ASSIGNED = tuple(_MODULES)          # the 10 graded architectures


def get_config(arch: str, smoke: bool = False):
    table = SMOKES if smoke else ARCHS
    if arch not in table:
        raise KeyError(f"unknown arch {arch!r}; have {sorted(table)}")
    return table[arch]


def list_archs() -> list[str]:
    return sorted(ARCHS)
