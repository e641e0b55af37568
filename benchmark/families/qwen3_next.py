"""The Qwen3-Next family: kungfu_tpu.models.transformer under a
configuration file whose keys are the source's (a Hugging Face `config.json`
of `model_type` qwen3_next): three Gated DeltaNet layers (a fused q, k, v, z
projection, a causal depthwise convolution, the gated delta rule over 16 key
and 32 value heads, a gated norm) to one gated softmax-attention layer (16
query heads on 2 key/value heads of 256, q/k norms a head, rotary over a
quarter of the head, a sigmoid gate a feature), norms with the scale 1 + w,
512 routed experts of which this chip holds a share beside a gated shared
expert, an untied head over a slice of the vocabulary. The system under test
is imported; the operation and byte counts, the batches and the plain
reference are the benchmark's own.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.olmoe import (EXPERT_KERNELS, cell_config,
                                      scope_own_ms)

REFERENCE_SAMPLES = 1  # one sequence_length-token sequence

# The program computes in bfloat16 and the reference in float32; router,
# decay, the delta rule's state, head and loss are float32 in both. Each
# tolerance is set from two readings on the chip at the published widths,
# 16,384 tokens and the initial parameters (PERF.md, PR 36): the largest
# error of the program over its seeds, and the error of the same program with
# every matrix rounded to float8_e4m3 (3 mantissa bits, the nearest precision
# below bfloat16), which has to fail.
# Gradients, as one vector |g - g_ref| / |g_ref|: 3.70 to 3.85 % over the
# seeds of PR 36's runs (PERF.md section 6 has the count); with 8-bit
# matrices 40.0 %. GRAD_RTOL is 2.1 times the largest reading and a fifth of
# the 8-bit one. The loss: 1.4e-6 to 2.6e-5 of itself; LOSS_RTOL is 7.7
# times the largest. The loss does not see 8-bit matrices (1.7e-4: the logits
# are small at the initial parameters): the gradients decide, as for the
# other families.
# What the gradients' limit sees of the scan, read at the timed sizes with a
# fault planted in `ops.gated_delta` (same seed; as it is 3.788 %): no decay
# (g = 0) 155.9 %, a backward pass that drops the carried state's cotangent
# 43.1 % (the DeltaNet mixers' leaves are two thirds of the gradient's norm:
# W_qkvz 1.80 of 2.66). What it cannot see, whole or a leaf at a time: the
# state rounded to bfloat16 at every chunk 3.784 % and the chunk decay
# rounded to bfloat16 3.788 % (A_log 3.93 and 4.21 % against 4.24 %, dt_bias,
# W_ba, the taps and W_qkvz likewise): at the initial parameters a layer's
# memory spans few chunks and that rounding is under the products' own. The
# rule is held to the recurrence by `tests/test_gated_delta.py`, where a
# decay of 0.99 rounded to bfloat16 is ten times the float32-state error.
# The router's choice is discrete: the program's normed token is a bfloat16
# and the reference's a float32, so a token whose 10th and 11th probabilities
# differ by less than that rounding takes another 10th expert;
# `differing_choices` counts them, and they are in the readings. A norm scale
# of w and not 1 + w, no q/k norm a head, no gate a feature, no gate on the
# shared expert, no convolution, no decay, keys of the wrong head read 16 %
# and more on a state in which they weigh (tests/test_qwen3_next_faults.py).
LOSS_RTOL = 2e-4
GRAD_RTOL = 8e-2

REFERENCE_QUERY_BLOCK = 256  # 16 heads x 256 x 16,384 float32 scores: 0.27 GB
REFERENCE_POSITION_BLOCK = 128  # 8 heads' states of a block: 0.07 GB; logits 10 MB
REFERENCE_HEAD_BLOCK = 2  # key heads, with their 4 value heads: 4.5 GB in all

LINEAR, FULL = "linear_attention", "full_attention"


def layer_types(cfg: dict) -> list:
    """The kind of each layer run here: every `full_attention_interval`-th
    is softmax attention, the others Gated DeltaNet."""
    every = cfg["full_attention_interval"]
    return [FULL if (l + 1) % every == 0 else LINEAR
            for l in range(cfg["num_hidden_layers"])]


def model_config(cfg: dict):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    if (cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu"
            or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1
            or cfg["rope_scaling"] is not None or cfg["use_sliding_window"]
            or not cfg["norm_topk_prob"]
            or cfg["linear_key_head_dim"] != cfg["linear_value_head_dim"]):
        raise ValueError("the qwen3_next family runs Qwen3-Next-80B-A3B's "
                         "layer as published: an untied head, silu, an expert "
                         "layer in every block, renormalised gates, no rope "
                         "scaling, no window, key and value heads of one size")
    recomputed = cfg["recomputed_layer_types"]

    def kind(layer_type):
        return (("mixer", "gated_delta" if layer_type == LINEAR else "attention"),
                ("layer_remat", layer_type in recomputed))

    return TransformerConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["moe_intermediate_size"],
        max_seq=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        positions="rope", rope_theta=float(cfg["rope_theta"]),
        rotary_share=float(cfg["partial_rotary_factor"]),
        qk_norm=True, norm_eps=cfg["rms_norm_eps"], norm_offset=True,
        ffn="moe", n_experts=cfg["published"]["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        tied_head=False,
        attn_core=cfg["attention_core"],
        flash_blocks=tuple(cfg["flash_blocks"]),
        flash_interpret=bool(cfg.get("flash_interpret", False)),
        head_size=cfg["head_dim"], n_kv_heads=cfg["num_key_value_heads"],
        q_gate=True, gates="renorm",
        experts_held=(cfg["first_expert_held"], cfg["num_experts"]),
        shared_ff=cfg["shared_expert_intermediate_size"], shared_gate=True,
        delta_heads=(cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
                     cfg["linear_key_head_dim"]),
        conv_taps=cfg["linear_conv_kernel_dim"],
        layer_kinds=tuple(kind(t) for t in layer_types(cfg)),
    )


def init(cfg: dict, seed: int):
    """The train state (the parameter tree), made on the device in one
    jitted call from the seed."""
    import jax

    from kungfu_tpu.models.transformer import init_transformer

    mc = model_config(cfg)
    return jax.jit(lambda key: init_transformer(key, mc))(jax.random.PRNGKey(seed))


def _with_routers(tree, of):
    """`tree` (a state or its gradients) with `of(router)` in the place of
    every layer's router matrix."""
    return {**tree, "layers": tuple({**stack, "router": of(stack["router"])}
                                    for stack in tree["layers"])}


def loss_fn(cfg: dict):
    """The model's loss. Where the configuration says `routers_trained`
    false the routers' matrices are constants of it: one chip's share of the
    experts gives a router only the part of its gradient that comes through
    the experts held, which says "send more here", and AdamW follows it at
    full speed whatever its size (11 times the balanced load on the held
    experts in 80 steps, PERF.md, PR 36); a deployment's router sees all ten
    of a token's experts. The routers route, and the tokens' gradient
    through their choice stands; their matrices get a gradient of zero."""
    import jax

    from kungfu_tpu.models.transformer import transformer_loss

    mc = model_config(cfg)
    if cfg["routers_trained"]:
        return lambda params, batch: transformer_loss(params, batch, mc)
    return lambda params, batch: transformer_loss(
        _with_routers(params, jax.lax.stop_gradient), batch, mc)


def trainable(state):
    """The part of the state the optimizer updates: all of it (a router
    that is not trained is in it with a gradient of zero)."""
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
    return dict(layers=layer_types(cfg),
                key_heads=cfg["linear_num_key_heads"],
                value_heads=cfg["linear_num_value_heads"],
                linear_head_dim=cfg["linear_key_head_dim"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
                rope_theta=float(cfg["rope_theta"]),
                rotary=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
                eps=cfg["rms_norm_eps"], top_k=cfg["num_experts_per_tok"],
                first_held=cfg["first_expert_held"],
                query_block=REFERENCE_QUERY_BLOCK,
                position_block=REFERENCE_POSITION_BLOCK,
                head_block=REFERENCE_HEAD_BLOCK)


def reference_loss_and_grads(cfg: dict, state, batch):
    """The reference's loss and gradients, the routers' set to zero where
    the configuration does not train them (`loss_fn`)."""
    import jax.numpy as jnp

    from benchmark.reference import qwen3_next as ref

    loss, grads = ref.loss_and_grads(state, batch, **_hyper(cfg))
    if not cfg["routers_trained"]:
        grads = _with_routers(grads, jnp.zeros_like)
    return loss, grads


def routing_stats(cfg: dict, state, batch) -> dict:
    """The program's routing counters on one host batch, as plain numbers,
    an entry a layer: token-choices computed per held expert, `held_rows`
    their sum, `dropped` (0 by construction) and the busiest held expert's
    load over the mean of all 512. Outside the step: the step returns a
    loss and nothing else."""
    import jax

    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    stats = jax.jit(lambda p, t: transformer.routing_stats(p, t, mc))(
        state, batch[:, :-1])
    return {k: np.asarray(v).tolist() for k, v in stats.items() if k != "chosen"}


def differing_choices(cfg: dict, state, batch) -> int:
    """Token-choices of the program's router that the reference's router
    does not make for the same token, over all layers."""
    import jax

    from benchmark.reference import qwen3_next as ref
    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    mine = np.asarray(jax.jit(
        lambda p, t: transformer.routing_stats(p, t, mc)["chosen"])(
            state, batch[:, :-1]))
    theirs = np.asarray(ref.chosen_experts(state, batch, **_hyper(cfg)))
    same = (mine[..., :, None] == theirs[..., None, :]).any(-1)
    return int(mine.size - same.sum())


def host_batch(cfg: dict, seed: int, i: int, n: int):
    """The i-th host batch of n samples: token ids (n, S + 1), each row one
    document of S + 1 tokens (no packing, no boundary mask); the loss shifts
    them by one. Ids are uniform over the rows of the vocabulary held here:
    over a share of the experts the step's work is the token-choices that
    land on the experts held, and a random router is balanced only over
    diverse inputs (PERF.md, PR 33)."""
    rng = np.random.default_rng([seed, i])
    return rng.integers(0, cfg["vocab_size"],
                        (n, cfg["sequence_length"] + 1), dtype=np.int32)


# -- operation and byte counts (2 a multiply-add; backward twice the forward;
#    nothing that is recomputed is counted) ----------------------------------


def expected_expert_passes(cfg: dict) -> float:
    """Routed-expert passes a token that fall on the experts held here, in
    expectation under a balanced router: top_k x held / published."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["published"]["num_experts"])


def delta_core_flops_per_sample(cfg: dict) -> float:
    """The gated delta rule of one layer over one sequence, as the
    recurrence states it, a value head and position: the decay of the state
    (dk x dv multiplies), S'^T k, the rank-one update k u^T and S^T q (2 dk
    dv each): 7 dk dv operations forward, twice that backward. The chunked
    form the program runs does more (the in-chunk products and the
    triangular system, about 11 dk dv forward at chunks of 64); what a
    kernel need not do is not counted."""
    d = cfg["linear_key_head_dim"]
    return (3 * 7.0 * d * cfg["linear_value_head_dim"]
            * cfg["linear_num_value_heads"] * cfg["sequence_length"])


def delta_core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """What the rule must move for one layer and sequence: forward reads q,
    k, v, g, beta and writes o; backward reads q, k, v, g, beta, do and
    writes dq, dk, dv, dg, dbeta: 6 arrays at the key heads, 5 at the value
    heads, and 6 of a float32 a value head and position. The chunk-boundary
    states the program keeps between its passes are its own choice and not
    counted."""
    d = cfg["linear_key_head_dim"]
    return cfg["sequence_length"] * (
        6.0 * cfg["linear_num_key_heads"] * d * itemsize
        + 5.0 * cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"] * itemsize
        + 6.0 * cfg["linear_num_value_heads"] * 4)


def attn_core_flops_per_sample(cfg: dict) -> float:
    """The softmax core of one full-attention layer over one sequence, the
    causal half: forward 2 matmuls (QK^T, PV), backward 4 (dV, dP, dQ, dK),
    each 2 operations a seen pair and feature, over the query heads. What
    the two-pass backward recomputes is not counted."""
    s = cfg["sequence_length"]
    return 6 * 2.0 * (s * s / 2) * cfg["num_attention_heads"] * cfg["head_dim"]


def attn_core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv: 6 arrays at the query heads and 6 at the key/value
    heads, of S x head size."""
    return (6.0 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])
            * cfg["sequence_length"] * cfg["head_dim"] * itemsize)


def mixer_params_per_token(cfg: dict, layer_type: str) -> float:
    """Parameters of one mixer that multiply every token. Gated DeltaNet:
    W_qkvz, W_ba, the convolution's taps over the q, k, v channels, W_o.
    Gated attention: W_q at twice the heads' width, W_k, W_v, W_o."""
    d = cfg["hidden_size"]
    if layer_type == LINEAR:
        k_width = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
        v_width = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
        return (d * (2 * k_width + 2 * v_width)
                + d * 2 * cfg["linear_num_value_heads"]
                + cfg["linear_conv_kernel_dim"] * (2 * k_width + v_width)
                + v_width * d)
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_width = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d * 2 * q_width + 2 * d * kv_width + q_width * d


def router_params_per_token(cfg: dict) -> float:
    """A layer's router, over all published experts."""
    return cfg["hidden_size"] * cfg["published"]["num_experts"]


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters that multiply every token: each layer's mixer, its router
    over all published experts, the shared expert and its gate, and the
    expected share of a token's routed-expert passes; the untied head over
    the rows held. Embedding lookups and the norms' scales do no matmul."""
    d = cfg["hidden_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    feed_forward = (router_params_per_token(cfg)
                    + 3 * d * cfg["shared_expert_intermediate_size"] + d
                    + expected_expert_passes(cfg) * expert)
    return (cfg["vocab_size"] * d
            + sum(mixer_params_per_token(cfg, t) + feed_forward
                  for t in layer_types(cfg)))


def flops_per_sample(cfg: dict) -> float:
    """Operations the forward and backward passes require for one sequence
    of `sequence_length` tokens: projections, convolutions, routers, shared
    experts, the held experts' expected share, the untied head, the delta
    rule of the linear layers and the causal half of the full ones; no
    recomputation. A router that is not trained has no weight-gradient
    product: one of its three passes is not required."""
    kinds = layer_types(cfg)
    idle = 0 if cfg["routers_trained"] else len(kinds) * router_params_per_token(cfg)
    return (2 * (3.0 * matmul_params_per_token(cfg) - idle) * cfg["sequence_length"]
            + kinds.count(LINEAR) * delta_core_flops_per_sample(cfg)
            + kinds.count(FULL) * attn_core_flops_per_sample(cfg))


# -- the per-layer metrics' common part -------------------------------------

CORE_SCOPES = {LINEAR: "gdn_core", FULL: "attn_full"}
_CORE_COUNTS = {LINEAR: (delta_core_flops_per_sample, delta_core_bytes_per_sample),
                FULL: (attn_core_flops_per_sample, attn_core_bytes_per_sample)}


def core_ms(record, trace, layer_type: str):
    """Own time a step of the device ops under `gdn_core` (the delta rule's
    three phases, forward and backward) or `attn_full` (the flash forward
    kernel, the two backward kernels and the row sums between them)."""
    return scope_own_ms(record, trace, {CORE_SCOPES[layer_type]})


def core_roofline_pct(record, trace, layer_type: str):
    """The least time the chip could take for the cores of the layers of one
    kind, the larger of their required operations over the bf16 peak and
    their required bytes over the memory peak (`peaks.json`), over the time
    they took, in %. None where there is no time to divide by."""
    from benchmark.harness import load_peaks

    ms = core_ms(record, trace, layer_type)
    if not ms:
        return None if ms is None else 0.0
    cfg = cell_config(record)
    peaks = load_peaks(record["device"]["kind"])
    flops, moved = _CORE_COUNTS[layer_type]
    n = record["samples_per_step"] * layer_types(cfg).count(layer_type)
    roof_s = max(n * flops(cfg) / peaks["bf16_flops"],
                 n * moved(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * roof_s / (ms * 1e-3)


def moe_ms(record, trace):
    """Own time a step under `moe`, with the grouped-matmul kernels that
    carry no scope (`families.olmoe.EXPERT_KERNELS`)."""
    return scope_own_ms(record, trace, {"moe"}, EXPERT_KERNELS)
