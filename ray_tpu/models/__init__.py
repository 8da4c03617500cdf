"""Model families (the flagship workloads of the framework).

- gpt2: pretraining flagship (BASELINE #2; the benchmark's
  `gpt2m_train_stream` cell measures it)
- llama: fine-tune/serving flagship with first-class LoRA and
  KV-cached decoding (BASELINE #4/#5)
- mixtral: sparse-MoE family exercising expert parallelism over the
  `ep` mesh axis (SURVEY §2.5)
"""

from ray_tpu.models import gpt2, llama, mixtral

__all__ = ["gpt2", "llama", "mixtral"]
