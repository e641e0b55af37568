"""Model zoo for the tracked benchmark configs (BASELINE.md).

The reference is model-agnostic (models live in user scripts /
tests/go/fakemodel size lists); here the models double as benchmark
workloads and as sharding showcases:
- mlp: MNIST SLP (the reference's minimum end-to-end example)
- transformer: flagship decoder-only LM with an explicit TP/DP/SP
  sharding plan (BERT-config capable); a layer's token mixer is a record
  of `mixers/`, and what mixers and layer share is `blocks.py`
- resnet: ResNet-50 (the headline throughput benchmark)
- fake: gradient-size lists for communication benchmarks without real math
  (parity: tests/go/fakemodel/fakemodel.go)
"""

from kungfu_tpu.models.mlp import MLP_PARITY_NOTE, init_mlp, mlp_apply, mlp_loss
from kungfu_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
    transformer_apply,
    transformer_loss,
    param_pspecs,
)

__all__ = [
    "MLP_PARITY_NOTE",
    "TransformerConfig",
    "init_mlp",
    "init_transformer",
    "mlp_apply",
    "mlp_loss",
    "param_pspecs",
    "transformer_apply",
    "transformer_loss",
]
