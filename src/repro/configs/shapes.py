"""Assigned input-shape grid + per-(arch x shape) input specs.

Every shape cell maps to ShapeDtypeStruct stand-ins (NO allocation) for the
step function the cell lowers:
  * train_*   -> ``train_step``  : {tokens, labels} (+ modality stubs)
  * prefill_* -> ``prefill_step``: {tokens} + zero cache
  * decode_* / long_* -> ``serve_step``: {tokens (B,1)} + full cache + pos
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class Shape:
    name: str
    kind: str  # train | prefill | decode
    seq: int
    batch: int


SHAPES: dict[str, Shape] = {
    "train_4k": Shape("train_4k", "train", 4_096, 256),
    "prefill_32k": Shape("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": Shape("decode_32k", "decode", 32_768, 128),
    "long_500k": Shape("long_500k", "decode", 524_288, 1),
}


def shape_applicable(cfg: ModelConfig, shape: Shape) -> tuple[bool, str]:
    """Assignment rules: long_500k only for sub-quadratic archs."""
    if shape.name == "long_500k":
        if cfg.family in ("ssm", "hybrid"):
            return True, "sub-quadratic (O(1)/O(window) decode state)"
        return False, (
            "full softmax attention: a 524288-token dense KV cache is "
            "architecturally quadratic in attention reads; skipped per "
            "assignment")
    return True, ""


def _tok(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: Shape) -> dict:
    """Model inputs (NOT params/cache — those come from ParamSpec trees)."""
    B = shape.batch
    dt = cfg.activation_dtype
    out: dict = {}
    if shape.kind == "train":
        out["tokens"] = _tok((B, shape.seq))
        out["labels"] = _tok((B, shape.seq))
    elif shape.kind == "prefill":
        out["tokens"] = _tok((B, shape.seq))
    else:  # decode
        out["tokens"] = _tok((B, 1))
    # modality stubs (assignment: frontend is a stub)
    if cfg.family == "vlm" and shape.kind != "decode":
        out["image_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.num_image_tokens, cfg.d_model), dt)
    if cfg.family == "audio" and shape.kind != "decode":
        out["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq, cfg.d_model), dt)
    return out


def memory_arg(cfg: ModelConfig, inputs: dict):
    """Extract the modality-stub memory arg the model's apply expects."""
    return inputs.get("image_embeds", inputs.get("frames"))
