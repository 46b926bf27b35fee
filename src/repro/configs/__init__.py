"""Architecture registry: ``--arch <id>`` resolution.

Every assigned architecture (plus the paper's own XNOR-CNN) registers here.
``get(name)`` also accepts ``<name>+xnor`` to produce the binary-quantized
variant of any LM arch (the paper's technique as a config axis).
"""

from __future__ import annotations

import dataclasses

from repro.configs.base import SHAPES, ArchConfig, ShapeConfig, shape_applicable
from repro.configs.qwen2_7b import CONFIG as qwen2_7b
from repro.configs.qwen3_4b import CONFIG as qwen3_4b
from repro.configs.phi4_mini_3_8b import CONFIG as phi4_mini_3_8b
from repro.configs.qwen3_14b import CONFIG as qwen3_14b
from repro.configs.xlstm_350m import CONFIG as xlstm_350m
from repro.configs.llama4_scout_17b_a16e import CONFIG as llama4_scout_17b_a16e
from repro.configs.moonlight_16b_a3b import CONFIG as moonlight_16b_a3b
from repro.configs.moonshot_v1_16b_a3b import CONFIG as moonshot_v1_16b_a3b
from repro.configs.recurrentgemma_2b import CONFIG as recurrentgemma_2b
from repro.configs.llama_3_2_vision_11b import CONFIG as llama_3_2_vision_11b
from repro.configs.whisper_tiny import CONFIG as whisper_tiny
from repro.configs.xnor_cnn import CONFIG as xnor_cnn

ALL: dict[str, ArchConfig] = {
    c.name: c
    for c in [
        qwen2_7b, qwen3_4b, phi4_mini_3_8b, qwen3_14b, xlstm_350m,
        llama4_scout_17b_a16e, moonlight_16b_a3b, moonshot_v1_16b_a3b,
        recurrentgemma_2b,
        llama_3_2_vision_11b, whisper_tiny, xnor_cnn,
    ]
}


def get(name: str) -> ArchConfig:
    quant = "none"
    if name.endswith("+xnor"):
        name, quant = name[: -len("+xnor")], "xnor"
    cfg = ALL[name]
    if quant != "none":
        cfg = dataclasses.replace(cfg, quant=quant,
                                  name=cfg.name + "+xnor")
    return cfg


__all__ = ["ALL", "SHAPES", "ArchConfig", "ShapeConfig", "get",
           "shape_applicable"]
