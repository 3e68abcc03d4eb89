"""repro.models — composable model definitions for all assigned archs."""

from . import (
    attention,
    encdec,
    frontends,
    layers,
    mamba,
    model,
    moe,
    params,
    transformer,
)
from .model import LM, ModelConfig, build_model

__all__ = [
    "LM",
    "ModelConfig",
    "attention",
    "build_model",
    "encdec",
    "frontends",
    "layers",
    "mamba",
    "model",
    "moe",
    "params",
    "transformer",
]
