"""bpe_transformer_tpu — a TPU-native LM pretraining framework.

Capability-parity rebuild of milasd/BPE-Transformer, designed TPU-first:

* host CPU: byte-level BPE tokenization (training, tiktoken-parity encoding,
  bounded-memory streaming);
* device (JAX/XLA/Pallas): transformer LM forward/backward, hand-rolled
  AdamW + cosine schedule, data-parallel / FSDP training via ``shard_map``
  over a ``jax.sharding.Mesh``, Pallas kernels for the hot ops.

Heavy JAX subpackages are imported lazily so tokenizer-only workflows never
pay for (or require) an accelerator runtime.
"""

from bpe_transformer_tpu.tokenization import BPETokenizer, BPETrainer, Tokenizer, train_bpe

__version__ = "0.1.0"

__all__ = ["BPETokenizer", "BPETrainer", "Tokenizer", "train_bpe", "__version__"]
