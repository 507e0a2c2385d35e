"""Model families (reference: the GPT and Llama models exercised by the
hybrid-parallel and semi-auto-parallel test suites, plus paddle.vision for
the conv families)."""

from . import (bert, falcon_h1, generation, gpt, hybrid_engine,  # noqa: F401
               llama, trinity_mini)
from .bert import BertConfig, BertForPretraining, BertModel  # noqa: F401
from .generation import (KVCache, PagedKVCache, gpt_generate,  # noqa: F401
                         llama_generate)
from .gpt import GPT, GPTConfig  # noqa: F401
from .llama import Llama, LlamaConfig  # noqa: F401

__all__ = ["bert", "gpt", "llama", "falcon_h1", "trinity_mini",
           "hybrid_engine", "generation", "GPT",
           "GPTConfig",
           "BertConfig", "BertModel", "BertForPretraining",
           "Llama", "LlamaConfig", "KVCache", "PagedKVCache", "gpt_generate",
           "llama_generate"]
