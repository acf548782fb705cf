from .checkpoint import (
    load_checkpoint,
    load_engine_checkpoint,
    save_checkpoint,
    save_engine_checkpoint,
)

__all__ = [
    "load_checkpoint",
    "load_engine_checkpoint",
    "save_checkpoint",
    "save_engine_checkpoint",
]
