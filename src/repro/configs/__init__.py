"""Assigned architecture configs (--arch <id>).

Each module defines CONFIG (the exact assigned full-scale config) and SMOKE
(a reduced same-family config for CPU smoke tests).  Full configs are never
allocated by the tests; their shapes are checked through
`repro.models.registry.input_specs` (ShapeDtypeStruct, no allocation).
"""

from __future__ import annotations

import importlib

ARCH_IDS = [
    "jamba_1_5_large_398b",
    "mamba2_370m",
    "qwen1_5_110b",
    "starcoder2_15b",
    "mistral_nemo_12b",
    "granite_8b",
    "internvl2_2b",
    "whisper_base",
    "phi3_5_moe_42b",
    "deepseek_v2_236b",
]

# accept dashed/dotted public ids too
ALIASES = {
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "mamba2-370m": "mamba2_370m",
    "qwen1.5-110b": "qwen1_5_110b",
    "starcoder2-15b": "starcoder2_15b",
    "mistral-nemo-12b": "mistral_nemo_12b",
    "granite-8b": "granite_8b",
    "internvl2-2b": "internvl2_2b",
    "whisper-base": "whisper_base",
    "phi3.5-moe-42b-a6.6b": "phi3_5_moe_42b",
    "deepseek-v2-236b": "deepseek_v2_236b",
}


def _module(name: str):
    name = ALIASES.get(name, name).replace("-", "_").replace(".", "_")
    return importlib.import_module(f"repro.configs.{name}")


def get_config(name: str):
    return _module(name).CONFIG


def get_smoke_config(name: str):
    return _module(name).SMOKE
