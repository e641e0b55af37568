"""The transformer family: kungfu_tpu.models.transformer under a
configuration file whose keys are the source's (a Hugging Face BERT
`config.json`). The system under test is imported; the operation count, the
batches and the plain reference are the benchmark's own.
"""

from __future__ import annotations

import numpy as np

REFERENCE_SAMPLES = 2  # sequences in the reference check

# The program computes in bfloat16 (8-bit mantissa, 2^-8 = 3.9e-3 a rounding)
# and the reference in float32; head and loss are float32 in both. Measured
# on the chip at bert_base's size over 42 runs (PR 23): the loss differs by
# 1.5e-6 to 6.3e-5 of itself, and the gradients, compared as one vector
# |g - g_ref| / |g_ref|, by 0.93 to 1.10 %. The tolerances are five times the
# largest loss error and not quite twice the largest gradient error: an
# 8-bit float's compute (3 mantissa bits, 16 times bfloat16's error) fails
# both. What the numbers cannot see (a bfloat16 head: the logits are small at
# the initial parameters) `harness.precision_faults` reads from the program.
LOSS_RTOL = 3e-4
GRAD_RTOL = 2e-2


def model_config(cfg: dict):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def init(cfg: dict, seed: int):
    """The train state (the parameter tree), made on the device in one
    jitted call from the seed."""
    import jax

    from kungfu_tpu.models.transformer import init_transformer

    mc = model_config(cfg)
    return jax.jit(lambda key: init_transformer(key, mc))(jax.random.PRNGKey(seed))


def loss_fn(cfg: dict):
    from kungfu_tpu.models.transformer import transformer_loss

    mc = model_config(cfg)
    return lambda params, batch: transformer_loss(params, batch, mc)


def trainable(state):
    """The part of the state the optimizer updates: all of it."""
    return state


def head_width(cfg: dict) -> int:
    """The output dimension of the head, which the configuration states in
    `head_dtype`."""
    return cfg["vocab_size"]


def program_loss_and_grads(cfg: dict):
    """The jitted (state, batch) -> (loss, gradients of `trainable(state)`),
    as one device computes them (no mesh): what the reference is compared
    with."""
    import jax

    return jax.jit(jax.value_and_grad(loss_fn(cfg)))


def reference_loss_and_grads(cfg: dict, state, batch):
    from benchmark.reference import transformer as ref

    return ref.loss_and_grads(state, batch, cfg["num_attention_heads"])


def host_batch(cfg: dict, seed: int, i: int, n: int):
    """The i-th host batch of n samples: token ids (n, S + 1), the loss
    shifts them by one. Ids are skewed towards the low ones (the cube of a
    uniform draw), as word frequencies are, so a model that learns the
    frequencies lowers its loss within a few steps."""
    rng = np.random.default_rng([seed, i])
    u = rng.random((n, cfg["max_position_embeddings"] + 1), dtype=np.float32)
    ids = (cfg["vocab_size"] * u ** 3).astype(np.int32)
    return np.minimum(ids, cfg["vocab_size"] - 1)


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply every token: the blocks' four projections
    and the tied output head. Embedding lookups, norms' scales and position
    rows do no matmul."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = d * 3 * d + d * d + d * f + f * d
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def flops_per_sample(cfg: dict) -> float:
    """Operations the forward and backward passes require for one sequence
    of `max_position_embeddings` tokens: 2 per multiply-add, backward twice
    the forward, causal attention counted as the half it needs (QK^T and PV
    over S/2 keys a query on average), recomputation not counted."""
    s, d = cfg["max_position_embeddings"], cfg["hidden_size"]
    matmul = 2 * matmul_params(cfg)
    attention = cfg["num_hidden_layers"] * 2 * s * d  # 2*(2*S*d)/2 a token
    return 3.0 * (matmul + attention) * s
