"""internvl2-2b — InternViT frontend (stubbed) + InternLM2 backbone [arXiv:2404.16821].

Source: the model card https://huggingface.co/OpenGVLab/InternVL2-2B; the
widths below are its ``llm_config``, the InternLM2-chat-1.8B language model
(24 layers, d_model 2048, 16 query / 8 KV heads of 128, SwiGLU d_ff 8192,
vocabulary 92553: InternLM2's 92544 plus InternVL's added tokens, RoPE base
1e6, untied LM head), as the paper and the model card give them.

The modality frontend is a STUB per assignment: ``input_specs()`` provides
precomputed patch embeddings (B, 256, d_model) prepended to the text stream
for training and the dry-run. Serving is text-positions-only: ``Engine.serve``
never passes ``prefix_embeds``, so prompt tokens stand in for the positions
that image embeddings would fill.
"""
from repro.configs.base import ModelConfig, register

FULL = ModelConfig(
    name="internvl2-2b",
    family="vlm",
    n_layers=24,
    d_model=2048,
    n_heads=16,
    n_kv_heads=8,
    d_ff=8192,
    vocab_size=92553,
    rope_theta=1000000.0,
    n_prefix_tokens=256,
    source="arXiv:2404.16821",
)

SMOKE = FULL.replace(
    name="internvl2-2b-smoke",
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    d_head=16,
    d_ff=128,
    vocab_size=256,
    n_prefix_tokens=4,
    q_chunk=8,
    remat=False,
)

register(FULL, SMOKE)
