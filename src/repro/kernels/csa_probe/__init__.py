from .ops import (
    csa_probe_pairs,
    csa_probe_search,
    csa_probe_search_with_lens,
    csa_probe_windows,
    supports,
)
from .ref import dedupe_topk_pool

__all__ = [
    "csa_probe_pairs",
    "csa_probe_search",
    "csa_probe_search_with_lens",
    "csa_probe_windows",
    "dedupe_topk_pool",
    "supports",
]
