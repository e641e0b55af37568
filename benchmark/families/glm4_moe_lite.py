"""The GLM-4-MoE-Lite family (GLM-4.7-Flash): kungfu_tpu.models.transformer
under a configuration file whose keys are the source's (a Hugging Face
`config.json` of `model_type` glm4_moe_lite): latent attention (a q latent of
768 and a key/value latent of 512 with their norms, 20 heads of 192 unrotated
and 64 rotated q/k features and 256 value features, one rotated key for all
heads), a dense first feed-forward and expert layers after it, sigmoid router
scores with a selection bias, the chosen scores renormalised and scaled, 64
routed experts of which this chip holds a share beside a shared expert, a
multi-token-prediction module with a loss of its own on the shared embedding
and head, an untied head over a slice of the vocabulary. The system under
test is imported; the operation and byte counts, the batches and the plain
reference are the benchmark's own.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.olmoe import cell_config, scope_own_ms

REFERENCE_SAMPLES = 1  # one sequence_length-token sequence (S + 2 ids)

# The program computes in bfloat16 and the reference in float32; router, both
# heads and both losses are float32 in both. Each tolerance is set from two
# readings on the chip at the published widths, 8,192 tokens and the initial
# parameters (PERF.md, PR 41): the largest error of the program over its
# seeds, and the error of the same program with every matrix rounded to
# float8_e4m3 (3 mantissa bits, the nearest precision below bfloat16), which
# has to fail.
# Gradients, as one vector |g - g_ref| / |g_ref|: 4.00 to 4.98 % over 23
# seeds; with 8-bit matrices 21.7 %. GRAD_RTOL is 1.6 times the largest
# reading and 0.37 of the 8-bit one (the OLMoE family's room, whose cell reads
# the same 4 to 5 %: a long causal core without a q/k norm carries it).
# The loss: 7e-8 to 7.0e-5 of itself over the same seeds; LOSS_RTOL is 2.9
# times the largest. The loss does not see 8-bit matrices (1.6e-4: the logits
# are small at the initial parameters): the gradients decide, as for the
# other families.
# The router's choice is discrete: the program's normed token is a bfloat16
# and the reference's a float32, so a token whose 4th and 5th biased scores
# differ by less than that rounding takes another 4th expert;
# `differing_choices` counts them (1,912 and 1,971 of a sequence's 163,840
# token-choices over the five expert layers, 1.2 %), and they are in the
# readings. No latent norm, a rotary key of each head's own, softmax scores,
# the bias in the weight or no bias, a scale of 1, the module fed t_i or
# given a head of its own or weighed 1 read 23 to 90 % on a state in which
# they weigh (tests/test_glm_4_7_flash_faults.py).
LOSS_RTOL = 2e-4
GRAD_RTOL = 8e-2

REFERENCE_QUERY_BLOCK = 256  # 20 heads x 256 x 8,192 float32 scores: 0.17 GB

DENSE, SPARSE = "dense", "sparse"


def layer_types(cfg: dict) -> list:
    """The feed-forward of each layer run here: the first
    `first_k_dense_replace` dense, the others expert layers."""
    return [DENSE if l < cfg["first_k_dense_replace"] else SPARSE
            for l in range(cfg["num_hidden_layers"])]


def model_config(cfg: dict):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    if (cfg["tie_word_embeddings"] or cfg["attention_bias"]
            or cfg["hidden_act"] != "silu" or cfg["rope_scaling"] is not None
            or cfg["partial_rotary_factor"] != 1 or not cfg["norm_topk_prob"]
            or cfg["topk_method"] != "noaux_tc" or cfg["n_group"] != 1
            or cfg["topk_group"] != 1 or cfg["n_shared_experts"] != 1
            or cfg["num_nextn_predict_layers"] != 1
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]
            or not 1 <= cfg["first_k_dense_replace"] < cfg["num_hidden_layers"]):
        raise ValueError("the glm4_moe_lite family runs GLM-4.7-Flash's layer "
                         "as published: an untied head, no bias, silu, no rope "
                         "scaling, every rotary feature turned, renormalised "
                         "sigmoid scores with a selection bias and no expert "
                         "groups, one shared expert, one multi-token-"
                         "prediction module, a key/value head a query head, "
                         "leading dense layers and expert layers after them")
    recomputed = cfg["recomputed_layer_types"]

    def kind(layer_type):
        dense = layer_type == DENSE
        return (("ffn", "swiglu" if dense else "moe"),
                ("d_ff", cfg["intermediate_size"] if dense
                 else cfg["moe_intermediate_size"]),
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
        norm_eps=cfg["rms_norm_eps"],
        ffn="moe", n_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        tied_head=False,
        attn_core=cfg["attention_core"],
        flash_blocks=tuple(cfg["flash_blocks"]),
        flash_interpret=bool(cfg.get("flash_interpret", False)),
        mixer="latent",
        latent_dims=(cfg["q_lora_rank"], cfg["kv_lora_rank"],
                     cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"]),
        router_scores="sigmoid", router_bias=True, gates="renorm",
        routed_scale=float(cfg["routed_scaling_factor"]),
        experts_held=(cfg["first_expert_held"], cfg["n_routed_experts"]),
        shared_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        mtp_depth=cfg["num_nextn_predict_layers"],
        mtp_weight=float(cfg["mtp_loss_weight"]),
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
    every expert layer's router matrix, the multi-token-prediction
    module's among them."""
    def routed(layer):
        return {**layer, "router": of(layer["router"])} if "router" in layer else layer

    return {**tree, "layers": tuple(routed(stack) for stack in tree["layers"]),
            "mtp": {**tree["mtp"], "layer": routed(tree["mtp"]["layer"])}}


def loss_fn(cfg: dict):
    """The model's loss, main + `mtp_loss_weight` x the module's. Where the
    configuration says `routers_trained` false the routers' matrices are
    constants of it, for the Qwen3-Next family's reason (`families.qwen3_next.
    loss_fn`; PERF.md, PR 36): one chip's share of the experts gives a
    router only the part of its gradient that comes through the experts
    held. The selection bias is a constant of the loss by what it is."""
    import jax

    from kungfu_tpu.models.transformer import transformer_loss

    mc = model_config(cfg)
    if cfg["routers_trained"]:
        return lambda params, batch: transformer_loss(params, batch, mc)
    return lambda params, batch: transformer_loss(
        _with_routers(params, jax.lax.stop_gradient), batch, mc)


def trainable(state):
    """The part of the state the optimizer updates: all of it (a router
    that is not trained and the selection bias are in it with a gradient of
    zero)."""
    return state


def head_width(cfg: dict) -> int:
    return cfg["vocab_size"]


def program_loss_and_grads(cfg: dict):
    """The jitted (state, batch) -> (loss, gradients of `trainable(state)`),
    as one device computes them (no mesh): what the reference is compared
    with."""
    import jax

    return jax.jit(jax.value_and_grad(loss_fn(cfg)))


def program_losses(cfg: dict, state, batch) -> dict:
    """The program's main and multi-token-prediction losses on one host
    batch, as plain numbers. Outside the step: the step returns their
    weighted sum and nothing else."""
    import jax

    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    return {k: float(v) for k, v in jax.jit(
        lambda p, b: transformer.transformer_losses(p, b, mc))(state, batch).items()}


def _hyper(cfg: dict) -> dict:
    return dict(heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
                rope=cfg["qk_rope_head_dim"], value=cfg["v_head_dim"],
                kv_rank=cfg["kv_lora_rank"], rope_theta=float(cfg["rope_theta"]),
                eps=cfg["rms_norm_eps"], top_k=cfg["num_experts_per_tok"],
                routed_scale=float(cfg["routed_scaling_factor"]),
                first_held=cfg["first_expert_held"],
                mtp_weight=float(cfg["mtp_loss_weight"]),
                query_block=REFERENCE_QUERY_BLOCK)


def reference_loss_and_grads(cfg: dict, state, batch):
    """The reference's loss and gradients, the routers' set to zero where
    the configuration does not train them (`loss_fn`)."""
    import jax.numpy as jnp

    from benchmark.reference import glm_4_7_flash as ref

    loss, grads = ref.loss_and_grads(state, batch, **_hyper(cfg))
    if not cfg["routers_trained"]:
        grads = _with_routers(grads, jnp.zeros_like)
    return loss, grads


def routing_stats(cfg: dict, state, batch) -> dict:
    """The program's routing counters on one host batch, as plain numbers,
    an entry an expert layer, the multi-token-prediction module's last:
    token-choices computed per held expert, `held_rows` their sum, `dropped`
    (0 by construction), the busiest held expert's load over the mean of all
    64, and `bias_moved`, the token-choices the selection bias changed.
    Outside the step: the step returns a loss and nothing else."""
    import jax

    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    stats = jax.jit(lambda p, t: transformer.routing_stats(p, t, mc))(
        state, batch[:, :-1])
    return {k: np.asarray(v).tolist() for k, v in stats.items() if k != "chosen"}


def differing_choices(cfg: dict, state, batch) -> int:
    """Token-choices of the program's router that the reference's router
    does not make for the same token, over all expert layers."""
    import jax

    from benchmark.reference import glm_4_7_flash as ref
    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    mine = np.asarray(jax.jit(
        lambda p, t: transformer.routing_stats(p, t, mc)["chosen"])(
            state, batch[:, :-1]))
    theirs = np.asarray(ref.chosen_experts(state, batch, **_hyper(cfg)))
    same = (mine[..., :, None] == theirs[..., None, :]).any(-1)
    return int(mine.size - same.sum())


def host_batch(cfg: dict, seed: int, i: int, n: int):
    """The i-th host batch of n samples: token ids (n, S + 2), each row one
    document of S + 2 tokens (no packing, no boundary mask); the main loss
    shifts them by one and the multi-token-prediction module's by two. Ids
    are uniform over the rows of the vocabulary held here: over a share of
    the experts the step's work is the token-choices that land on the experts
    held, and a random router is balanced only over diverse inputs (PERF.md,
    PR 33)."""
    rng = np.random.default_rng([seed, i])
    return rng.integers(0, cfg["vocab_size"],
                        (n, cfg["sequence_length"] + 2), dtype=np.int32)


# -- operation and byte counts (2 a multiply-add; backward twice the forward;
#    nothing that is recomputed is counted) ----------------------------------


def blocks(cfg: dict) -> list:
    """The feed-forward of every block a step runs: the layers, and the
    multi-token-prediction module's, an expert layer."""
    return layer_types(cfg) + [SPARSE] * cfg["num_nextn_predict_layers"]


def expected_expert_passes(cfg: dict) -> float:
    """Routed-expert passes a token that fall on the experts held here, in
    expectation under a balanced router: top_k x held / published."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["published"]["n_routed_experts"])


def core_flops_per_sample(cfg: dict) -> float:
    """The softmax core of one latent-attention layer over one sequence, the
    causal half: forward 2 matmuls (QK^T over 192 + 64 features, PV over
    256), backward 4 (dV, dP, dQ, dK), each 2 operations a seen pair and
    feature, over the heads. What the two-pass backward recomputes is not
    counted. The counts are the layer's, whatever implements the core."""
    s = cfg["sequence_length"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (3 * 2.0 * (s * s / 2) * cfg["num_attention_heads"]
            * (qk + cfg["v_head_dim"]))


def core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv: q, k, dq, dk, twice each but the cotangents (6
    arrays), at the q/k head size, and v, o, do, dv likewise (6) at the
    value head size, of S positions and all heads each. (A core that read
    the one rotary key unlaid would move less; the layer as published lays
    k out a head.)"""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (6.0 * (qk + cfg["v_head_dim"]) * cfg["num_attention_heads"]
            * cfg["sequence_length"] * itemsize)


def mixer_params_per_token(cfg: dict) -> float:
    """Parameters of one latent-attention mixer that multiply every token:
    W_q_down, W_q_up, W_kv_down (the rotary key's columns among them),
    W_kv_up, W_o. The latents' norms do no matmul."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def router_params_per_token(cfg: dict) -> float:
    """A layer's router, over all published experts."""
    return cfg["hidden_size"] * cfg["published"]["n_routed_experts"]


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters that multiply every token: each block's mixer; the dense
    feed-forward, or the router over all published experts, the shared
    expert and the expected share of a token's routed-expert passes; the
    multi-token-prediction module's (2D, D) projection; the untied head over
    the rows held, once for the main loss and once for the module's (the
    published loss has both). Embedding lookups and the norms' scales do no
    matmul."""
    d = cfg["hidden_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    sparse = (router_params_per_token(cfg)
              + cfg["n_shared_experts"] * expert
              + expected_expert_passes(cfg) * expert)
    feed_forward = {DENSE: 3 * d * cfg["intermediate_size"], SPARSE: sparse}
    modules = cfg["num_nextn_predict_layers"]
    return ((1 + modules) * cfg["vocab_size"] * d + modules * 2 * d * d
            + sum(mixer_params_per_token(cfg) + feed_forward[t]
                  for t in blocks(cfg)))


def flops_per_sample(cfg: dict) -> float:
    """Operations the forward and backward passes require for one sequence
    of `sequence_length` tokens: the projections, routers, shared experts,
    the dense layer, the held experts' expected share, the module's
    projection, both head passes and the causal half of every block's core;
    no recomputation. A router that is not trained has no weight-gradient
    product: one of its three passes is not required."""
    kinds = blocks(cfg)
    idle = 0 if cfg["routers_trained"] else (
        kinds.count(SPARSE) * router_params_per_token(cfg))
    return (2 * (3.0 * matmul_params_per_token(cfg) - idle) * cfg["sequence_length"]
            + len(kinds) * core_flops_per_sample(cfg))


# -- the per-layer metrics' common part -------------------------------------

CORE_SCOPE = "attn_latent"


def core_ms(record, trace):
    """Own time a step of the device ops under `attn_latent`: the flash
    forward kernel, the two backward kernels and the row sums between them,
    of every block's core, the multi-token-prediction module's among them."""
    return scope_own_ms(record, trace, {CORE_SCOPE})


def core_roofline_pct(record, trace):
    """The least time the chip could take for the cores of every block, the
    larger of their required operations over the bf16 peak and their
    required bytes over the memory peak (`peaks.json`), over the time they
    took, in %. None where there is no time to divide by."""
    from benchmark.harness import load_peaks

    ms = core_ms(record, trace)
    if not ms:
        return None if ms is None else 0.0
    cfg = cell_config(record)
    peaks = load_peaks(record["device"]["kind"])
    n = record["samples_per_step"] * len(blocks(cfg))
    roof_s = max(n * core_flops_per_sample(cfg) / peaks["bf16_flops"],
                 n * core_bytes_per_sample(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * roof_s / (ms * 1e-3)


def mixer_ms(record, trace):
    """Own time a step under `attn` that is not the cores': `attn` less
    `attn_latent`, of every block."""
    whole = scope_own_ms(record, trace, {"attn"})
    core = core_ms(record, trace)
    if whole is None or core is None:
        return None
    return whole - core
