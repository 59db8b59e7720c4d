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


# the paper's own benchmark input shapes (Fig. 1)
SHAPES = {
    "train_4k": dict(seq_len=4096, global_batch=256, kind="train"),
    "prefill_32k": dict(seq_len=32768, global_batch=32, kind="prefill"),
    "decode_32k": dict(seq_len=32768, global_batch=128, kind="decode"),
    "long_500k": dict(seq_len=524288, global_batch=1, kind="decode"),
}
