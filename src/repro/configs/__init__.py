"""Config registry — importing this package registers every architecture."""

from repro.configs import base
from repro.configs import (  # noqa: F401  (registration side effects)
    bert4rec,
    colbert_base,
    dcn_v2,
    dlrm_rm2,
    gin_tu,
    granite_moe_3b_a800m,
    gte_moderncolbert,
    minitron_4b,
    mixtral_8x7b,
    qwen2_5_32b,
    stablelm_3b,
    wide_deep,
)
from repro.configs.base import ArchEntry, ShapeSpec, all_archs, get

ASSIGNED = [
    "granite-moe-3b-a800m", "mixtral-8x7b", "stablelm-3b", "qwen2.5-32b",
    "minitron-4b", "gin-tu", "dlrm-rm2", "dcn-v2", "wide-deep", "bert4rec",
]

__all__ = ["ArchEntry", "ShapeSpec", "all_archs", "get", "ASSIGNED", "base"]
