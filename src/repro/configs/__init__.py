"""Architecture configs: one module per assigned architecture + the six
paper apps.  ``get_config(name)`` is the registry entry point."""
from repro.configs.base import (ArchConfig, get_config, register,
                                list_archs, SHAPES, ShapeSpec)

# import for registration side effects
from repro.configs import (  # noqa: F401
    starcoder2_3b, mistral_nemo_12b, internlm2_20b, qwen1_5_32b,
    mamba2_1_3b, recurrentgemma_9b, qwen2_moe_a2_7b, mixtral_8x22b,
    whisper_medium, llama3_2_vision_90b, granite_4_0_h_small, paper_apps)

__all__ = ["ArchConfig", "get_config", "register", "list_archs", "SHAPES",
           "ShapeSpec"]
