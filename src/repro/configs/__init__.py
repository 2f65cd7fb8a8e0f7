"""Architecture registry: ``get(arch_id)`` / ``REGISTRY`` / ``--arch`` ids."""
from __future__ import annotations

from repro.configs import (
    deepseek_v2_lite,
    din,
    graphsage_reddit,
    grok_1_314b,
    llama4_maverick_400b,
    mind,
    nemotron_4_340b,
    olmo_1b,
    paper_twotower,
    qwen1_5_4b,
    two_tower_retrieval,
    wide_deep,
)
from repro.configs.base import ArchSpec, Shape

_MODULES = [
    qwen1_5_4b, olmo_1b, nemotron_4_340b, grok_1_314b, llama4_maverick_400b,
    graphsage_reddit, wide_deep, two_tower_retrieval, mind, din,
    paper_twotower, deepseek_v2_lite,
]

REGISTRY: dict[str, ArchSpec] = {m.ARCH.arch_id: m.ARCH for m in _MODULES}

# The 10 assigned architectures (paper-twotower and deepseek-v2-lite are
# extra, not in the dry-run grid).
ASSIGNED = [a for a in REGISTRY
            if a not in ("paper-twotower", "deepseek-v2-lite")]


def get(arch_id: str) -> ArchSpec:
    if arch_id not in REGISTRY:
        raise KeyError(
            f"unknown arch {arch_id!r}; available: {sorted(REGISTRY)}"
        )
    return REGISTRY[arch_id]


def grid_cells():
    """All (arch_id, shape_name) dry-run cells — the 40-cell grid."""
    cells = []
    for aid in ASSIGNED:
        for shape_name in REGISTRY[aid].shapes:
            cells.append((aid, shape_name))
    return cells
