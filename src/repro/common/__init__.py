from repro.common.utils import (
    enable_compilation_cache,
    cdiv,
    round_up,
    tree_size,
    tree_bytes,
    human_bytes,
    human_number,
)

__all__ = [
    "enable_compilation_cache",
    "cdiv",
    "round_up",
    "tree_size",
    "tree_bytes",
    "human_bytes",
    "human_number",
]
