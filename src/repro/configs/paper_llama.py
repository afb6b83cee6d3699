"""The paper's own model family: LLaMA-2-7B structure (dry-run scale) and
small CPU-trainable variants (``launch/train.py``,
``examples/quickstart.py``)."""
from repro.models.config import ModelConfig
from repro.models.registry import register_arch


def full() -> ModelConfig:
    return ModelConfig(
        name="llama2-7b", family="dense",
        num_layers=32, d_model=4096, num_heads=32, num_kv_heads=32,
        d_ff=11008, vocab_size=32000, head_dim=128,
    )


def smoke() -> ModelConfig:
    return ModelConfig(
        name="llama2-7b-smoke", family="dense",
        num_layers=4, d_model=256, num_heads=4, num_kv_heads=4,
        d_ff=512, vocab_size=512, head_dim=64,
        q_chunk=16, kv_chunk=16,
    )


def tiny_lm() -> ModelConfig:
    """~100M llama-style LM, trainable on CPU.

    All K dims (d_model=768, d_ff=2048) are multiples of 128 so every
    linear supports fine-grained group-128 quantization. f32 on CPU
    (bf16 is emulated and slow there)."""
    return ModelConfig(
        name="tiny-lm-100m", family="dense",
        num_layers=14, d_model=768, num_heads=12, num_kv_heads=12,
        d_ff=2048, vocab_size=512, head_dim=64, dtype="float32",
        q_chunk=64, kv_chunk=64, remat=False,
    )


register_arch("llama2-7b", full, smoke)


def bench_lm() -> ModelConfig:
    """~30M llama-style LM, trainable on CPU (``examples/quickstart.py``).
    K dims (512, 1536) are multiples of 128 for group-128 quantization."""
    return ModelConfig(
        name="bench-lm-30m", family="dense",
        num_layers=8, d_model=512, num_heads=8, num_kv_heads=8,
        d_ff=1536, vocab_size=512, head_dim=64, dtype="float32",
        q_chunk=512, kv_chunk=512, remat=False,
    )
