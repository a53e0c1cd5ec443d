"""phi3-medium-14b [dense] — RoPE SwiGLU GQA [arXiv:2404.14219].

Widths and vocabulary from ``microsoft/Phi-3-medium-4k-instruct``'s
``config.json``: 32064 token ids (Phi-3-small's tiktoken vocabulary of
100352 is another model's)."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="phi3-medium-14b", family="dense",
    source="arXiv:2404.14219; huggingface.co/microsoft/"
           "Phi-3-medium-4k-instruct config.json",
    n_layers=40, d_model=5120, n_heads=40, n_kv_heads=10,
    d_ff=17920, vocab_size=32064, head_dim=128,
)
