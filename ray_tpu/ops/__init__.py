"""TPU kernels and fused ops (Pallas where it wins, XLA elsewhere)."""

from ray_tpu.ops.attention import flash_attention

__all__ = ["flash_attention"]
