"""The Ouro family (ByteDance/Ouro-2.6B, a looped language model):
kungfu_tpu.models.transformer under a configuration file whose keys are the
source's (a Hugging Face `config.json` of `model_type` ouro): one stack of
layers run `total_ut_steps` times on one set of weights, a norm on both sides
of each branch of a layer, the final norm at the end of every loop step, an
exit gate on the normed state, the expected cross-entropy over the loop
steps' head passes less an entropy term, rotary positions, plain multi-head
attention through the flash core, a gated-silu feed-forward, an untied head.
The system under test is imported; the operation and byte counts, the
batches and the plain reference are the benchmark's own.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.olmoe import cell_config

REFERENCE_SAMPLES = 1  # one sequence_length-token sequence

# The program computes in bfloat16 and the reference in float32; norm
# statistics, the gate, the exit distribution, head and loss are float32 in
# both. Each tolerance is set from two readings on the chip at the published
# widths, 4,096 tokens, four loop steps and the initial parameters (my chip
# runs, PR 48; PERF.md section 6): the largest error of the program over its
# seeds, and the error of the same program with every matrix rounded to
# float8_e4m3 (3 mantissa bits, the nearest precision below bfloat16), which
# has to fail.
# Gradients, as one vector |g - g_ref| / |g_ref|: 0.0187 to 0.0315 over 17
# seeds (median 0.0238; every leaf 0.015 to 0.026 where the whole
# reads 0.023, but the gate's bias, 0.002 to 0.008); 0.3078 and 0.3288 in 8
# bits. The limit stands between, 1.9 times the largest reading and a fifth
# of the smaller 8-bit one.
# (OLMoE's cell reads 0.04 to 0.05 through the same core: there q/k norms and
# a router stand in the path; here the error is the matmuls' rounding through
# 32 layer applications, and the norms on both sides of every branch hold it.)
# The loss: 6.8e-7 to 4.3e-5 over those seeds (2.8e-5 the first), 2.0e-5 and
# 5.4e-5 in 8 bits: the precision hardly moves it, so the limit is the other
# transformer cells' (seven times the first reading), and the 8-bit program
# is refused by its gradients and not by its loss.
# Three loop steps for four, no norm between loop steps, the head on the
# un-normed state, the last share as lambda times what is left, the
# entropy's sign, a branch without its second norm, a gate without a
# gradient and a loop step without one read over twice `GRAD_RTOL` on a state
# in which they weigh; bfloat16 logits the numbers cannot see, and
# `harness.precision_faults` reads them off the program
# (tests/test_ouro_faults.py).
LOSS_RTOL = 2e-4
GRAD_RTOL = 6e-2

REFERENCE_QUERY_BLOCK = 256  # 16 heads x 256 x 4,096 float32 scores: 67 MB

FULL = "full_attention"


def layer_types(cfg: dict) -> list:
    """The kind of each layer run here: the published list as far as the
    depth (the file keeps it whole)."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def model_config(cfg: dict):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    if (cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu"
            or cfg["rope_scaling"] is not None or cfg["use_sliding_window"]
            or cfg["sliding_window"] is not None
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]
            or cfg["early_exit_threshold"] != 1
            or layer_types(cfg) != [FULL] * cfg["num_hidden_layers"]):
        raise ValueError("the ouro family runs Ouro-2.6B's layer as "
                         "published: an untied head, silu, no rope scaling, "
                         "no window, plain multi-head attention in every "
                         "layer, no early exit in training")
    return TransformerConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        positions="rope", rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        ffn="swiglu", tied_head=False,
        attn_core=cfg["attention_core"],
        flash_blocks=tuple(cfg["flash_blocks"]),
        flash_interpret=bool(cfg.get("flash_interpret", False)),
        head_size=cfg["head_dim"],
        layer_remat=FULL in cfg["recomputed_layer_types"],
        loop_steps=cfg["total_ut_steps"],
        exit_entropy_coef=cfg["exit_entropy_coef"],
        post_norms=True,
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
    return cfg["vocab_size"]


def program_loss_and_grads(cfg: dict):
    """The jitted (state, batch) -> (loss, gradients of `trainable(state)`),
    as one device computes them (no mesh): what the reference is compared
    with."""
    import jax

    return jax.jit(jax.value_and_grad(loss_fn(cfg)))


def _hyper(cfg: dict) -> dict:
    return dict(n_heads=cfg["num_attention_heads"], eps=cfg["rms_norm_eps"],
                theta=float(cfg["rope_theta"]),
                loop_steps=cfg["total_ut_steps"],
                beta=cfg["exit_entropy_coef"],
                query_block=REFERENCE_QUERY_BLOCK)


def reference_loss_and_grads(cfg: dict, state, batch):
    from benchmark.reference import ouro as ref

    return ref.loss_and_grads(state, batch, **_hyper(cfg))


def loop_losses(cfg: dict, state, batch) -> dict:
    """The program's parts of the loss on one host batch, as plain numbers:
    `main`, `loop` and `exit_share` a loop step, `exit_entropy`. Outside the
    step: the step returns a loss and nothing else."""
    import jax

    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    parts = jax.jit(lambda p, b: transformer.transformer_losses(p, b, mc))(
        state, batch)
    return {k: np.asarray(v).tolist() for k, v in parts.items()}


def host_batch(cfg: dict, seed: int, i: int, n: int):
    """The i-th host batch of n samples: token ids (n, S + 1), each row one
    document of S + 1 tokens (no packing, no boundary mask); the loss shifts
    them by one. Ids are uniform over the whole vocabulary: no expert and no
    router is here for a skew to load."""
    rng = np.random.default_rng([seed, i])
    return rng.integers(0, cfg["vocab_size"],
                        (n, cfg["sequence_length"] + 1), dtype=np.int32)


# -- operation and byte counts (2 a multiply-add; backward twice the forward;
#    nothing that is recomputed is counted). The required work is of the
#    applications, not of the parameters: a layer's matrices multiply every
#    token once a loop step, and so does the head ------------------------------


def layer_params_per_token(cfg: dict) -> int:
    """Parameters of one layer that multiply a token in one application:
    W_q, W_k, W_v, W_o and the feed-forward's three."""
    d = cfg["hidden_size"]
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4 * d * q_width + 3 * d * cfg["intermediate_size"]


def matmul_params_per_token(cfg: dict) -> int:
    """Parameter applications a token and forward pass: every layer and the
    untied head once a loop step, the exit gate's column once a loop step
    but the last (the last gate is read by nothing: p_T is what the others
    left). Embedding lookups and the norms' scales do no matmul."""
    T, d = cfg["total_ut_steps"], cfg["hidden_size"]
    return (T * cfg["num_hidden_layers"] * layer_params_per_token(cfg)
            + T * cfg["vocab_size"] * d + (T - 1) * d)


def core_flops_per_sample(cfg: dict) -> float:
    """The softmax core of one layer application over one sequence, the
    causal half: forward 2 matmuls (QK^T, PV), backward 4 (dV, dP, dQ, dK),
    each 2 operations a seen pair and feature, over the heads. What the
    two-pass backward recomputes is not counted."""
    s = cfg["sequence_length"]
    return 6 * 2.0 * (s * s / 2) * cfg["num_attention_heads"] * cfg["head_dim"]


def core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv: 12 arrays of S x heads x head size. 0.2 GB against
    206 GFLOP an application: at head size 128 the core is compute-bound."""
    return (12.0 * cfg["sequence_length"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * itemsize)


def core_applications(cfg: dict) -> int:
    """Layer applications a forward pass: loop steps x layers."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def flops_per_sample(cfg: dict) -> float:
    """Operations the forward and backward passes require for one sequence
    of `sequence_length` tokens: T x L layer applications, T head passes,
    T - 1 gates and the causal half of T x L cores; no recomputation."""
    return (3.0 * 2 * matmul_params_per_token(cfg) * cfg["sequence_length"]
            + core_applications(cfg) * core_flops_per_sample(cfg))


# -- the per-layer metrics' common part -------------------------------------


def scope_ms(record, trace, wanted):
    """`trace_reduce.scope_ms`: own time a step of the device ops whose scope
    names `wanted(names)` holds of. None without a scope table or a traced
    chip, as everywhere; 0.0 where the program has a table and no such op
    (nothing ran there: a step of another family, or of the parent commit),
    as `families.olmoe.scope_own_ms` reads it."""
    from benchmark import trace_reduce

    if not record.get("scopes") or not trace or not trace["chips"]:
        return None
    ms = trace_reduce.scope_ms(record, trace,
                               lambda phase, names: wanted(names))
    return 0.0 if ms is None else ms


def core_ms(record, trace):
    """Own time a step of the device ops under `attn_core`: the flash forward
    kernel, the two backward kernels, the row sums between them and the
    layout copies at their doors, of all T x L applications."""
    return scope_ms(record, trace, lambda names: "attn_core" in names)


def core_roofline_pct(record, trace):
    """The least time the chip could take for the T x L cores a step, the
    larger of their required operations over the bf16 peak and their
    required bytes over the memory peak (`peaks.json`), over the time they
    took, in %. None where there is no time to divide by, 0.0 of no time at
    all."""
    from benchmark.harness import load_peaks

    ms = core_ms(record, trace)
    if not ms:
        return None if ms is None else 0.0
    cfg = cell_config(record)
    peaks = load_peaks(record["device"]["kind"])
    n = record["samples_per_step"] * core_applications(cfg)
    roof_s = max(n * core_flops_per_sample(cfg) / peaks["bf16_flops"],
                 n * core_bytes_per_sample(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * roof_s / (ms * 1e-3)
