from bigdl_tpu.models.transformer.generate import (GenerationConfig,
                                                    beam_search, generate)
from bigdl_tpu.models.transformer.model import (EvaByteLM, KeyeLM, KimiLM,
                                                PreNormBlock,
                                                TransformerBlock,
                                                TransformerLM)

__all__ = ["TransformerBlock", "TransformerLM", "PreNormBlock", "EvaByteLM",
           "KeyeLM", "KimiLM",
           "GenerationConfig",
           "generate", "beam_search"]
