"""TPU kernels and fused ops (Pallas where it wins, XLA elsewhere)."""

from ray_tpu.ops.attention import (
    FLASH_RESIDUALS,
    checkpoint_block,
    flash_attention,
)

__all__ = ["FLASH_RESIDUALS", "checkpoint_block", "flash_attention"]
