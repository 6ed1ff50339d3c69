"""Config registry: ``get_config("<arch-id>")`` -> ArchConfig.

Assigned architecture ids use the public pool spelling (dashes); module
names use underscores.
"""
from .base import (ArchConfig, CELUConfig, MoEConfig, ShapeConfig, SSMConfig,
                   TrainConfig, VFLConfig, XLSTMConfig, LONG_CONTEXT_WINDOW,
                   SHAPES)

ARCH_IDS = (
    "hymba-1.5b",
    "deepseek-7b",
    "llama-3.2-vision-90b",
    "granite-moe-3b-a800m",
    "smollm-360m",
    "seamless-m4t-large-v2",
    "llama4-scout-17b-a16e",
    "yi-34b",
    "xlstm-125m",
    "codeqwen1.5-7b",
    "lfm2-8b-a1b",
)

DLRM_IDS = ("wdl-criteo", "dssm-avazu")


def _module_name(arch_id: str) -> str:
    return arch_id.replace("-", "_").replace(".", "_")


def get_config(arch_id: str):
    """ArchConfig for assigned archs; DLRMConfig for the paper's DLRMs."""
    import importlib
    mod = importlib.import_module(f".{_module_name(arch_id)}", __package__)
    return mod.CONFIG


def arch_for_shape(arch_id: str, shape_name: str):
    """Resolve the (possibly sliding-window) variant used for a shape.

    long_500k on attention archs uses the sliding-window variant
    (DESIGN §3 long_500k policy); SSM/hybrid archs decode in O(1) state
    and keep full config."""
    cfg = get_config(arch_id)
    if shape_name == "long_500k" and cfg.family != "ssm":
        return cfg.with_sliding_window(LONG_CONTEXT_WINDOW)
    return cfg
