"""Architecture registry: --arch <id> resolves here."""
from .base import ArchConfig  # noqa: F401

from . import (chameleon_34b, granite_moe_1b, phi35_moe, xlstm_350m,
               whisper_medium, mistral_nemo_12b, moonlight_16b_a3b, qwen3_4b,
               qwen25_3b, phi3_mini, zamba2_27b)

REGISTRY = {m.CONFIG.name: m.CONFIG for m in (
    chameleon_34b, granite_moe_1b, phi35_moe, xlstm_350m, whisper_medium,
    mistral_nemo_12b, moonlight_16b_a3b, qwen3_4b, qwen25_3b, phi3_mini,
    zamba2_27b)}

ARCH_IDS = sorted(REGISTRY)


def get_config(name: str, *, tiny: bool = False) -> ArchConfig:
    cfg = REGISTRY[name]
    return cfg.tiny() if tiny else cfg
