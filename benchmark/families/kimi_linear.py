"""The Kimi Linear family (Kimi-Linear-48B-A3B-Instruct):
kungfu_tpu.models.transformer under a configuration file whose keys are the
source's (a Hugging Face `config.json` of `model_type` kimi_linear): three
Kimi Delta Attention layers (32 heads of 128 for q, k and v alike behind
convolutions of 4 taps, a delta rule whose decay is a number a key feature, a
norm a head under a sigmoid gate) to one latent-attention layer with no q
latent and no positions (32 heads of 128 + 64 q/k features, the 64 one key
shared by all heads, on value heads of 128), a dense first feed-forward and
expert layers after it, sigmoid router scores with a selection bias, the
chosen scores renormalised and scaled, 256 routed experts of which this chip
holds a share beside a shared expert, an untied head over a slice of the
vocabulary. The system under test is imported; the operation and byte counts,
the batches and the plain reference are the benchmark's own.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.olmoe import cell_config, scope_own_ms

REFERENCE_SAMPLES = 1  # one sequence_length-token sequence

# The program computes in bfloat16 and the reference in float32; router, the
# decay and beta with their projections, the delta rule's state, head and loss
# are float32 in both. Each tolerance is set from two readings on the chip at
# the published widths, 16,384 tokens and the initial parameters (PERF.md,
# PR 69): the largest error of the program over its seeds, and the error of
# the same program with every matrix rounded to float8_e4m3 (3 mantissa bits,
# the nearest precision below bfloat16), which has to fail.
# Gradients, as one vector |g - g_ref| / |g_ref|: 3.76 to 4.12 % over 17
# seeds; with 8-bit matrices 42.1 %. GRAD_RTOL is 1.9 times the largest
# reading and 0.19 of the 8-bit one (the Qwen3-Next family's limit, whose
# cell has the same three-to-one stack of a delta rule under softmax
# attention). The loss: 9e-7 to 4.4e-5 of itself over the same seeds;
# LOSS_RTOL, the harness's accepted cells' limit, is 4.5 times the largest
# and 4.9 times the first reading (4.05e-5). With 8-bit matrices the loss
# reads 3.3e-4, over its limit by little (the logits are small at the initial
# parameters): the gradients decide, as for the other families.
# What the gradients' limit cannot see at the initial parameters, whole: a
# state or a decay rounded to bfloat16 (a layer's memory spans few chunks
# there, PR 36's finding for the Gated DeltaNet layers). The rule is held to
# the recurrence by `tests/test_kda.py`, where a decay of 0.99 a feature
# rounded to bfloat16 is ten times the float32-state error, and under a decay
# of e^-20 a position beside one of e^-0.001.
# The router's choice is discrete: the program's normed token is a bfloat16
# and the reference's a float32, so a token whose 8th and 9th biased scores
# differ by less than that rounding takes another 8th expert;
# `differing_choices` counts them (11,745 of a sequence's 524,288
# token-choices over the four expert layers, 2.2 %), and they are in the
# readings. A decay a head in the place of one a feature, no sigmoid gate, a
# key turned by its position and a scale of 1 / sqrt(128) read far over the
# limit on a state in which they weigh (tests/test_kimi_linear_faults.py).
LOSS_RTOL = 2e-4
GRAD_RTOL = 8e-2

REFERENCE_QUERY_BLOCK = 256  # 32 heads x 256 x 16,384 float32 scores: 0.54 GB
REFERENCE_POSITION_BLOCK = 128  # 8 heads' states of a block: 0.07 GB; logits 10 MB
REFERENCE_HEAD_BLOCK = 8  # KDA heads, q, k, v, g and the gate in float32: 0.5 GB

KDA, MLA = "kda", "mla"
DENSE, SPARSE = "dense", "sparse"


def layer_types(cfg: dict) -> list:
    """(mixer, feed-forward) of each layer run here, as `kda_dense`,
    `kda_sparse`, `mla_sparse` (or `mla_dense`): layer l, from 0, is latent
    attention iff l + 1 is in `linear_attn_config.full_attn_layers`, else
    KDA; its feed-forward is dense iff l < `first_k_dense_replace`."""
    full = set(cfg["linear_attn_config"]["full_attn_layers"])
    return [f"{MLA if l + 1 in full else KDA}_"
            f"{DENSE if l < cfg['first_k_dense_replace'] else SPARSE}"
            for l in range(cfg["num_hidden_layers"])]


def _count(cfg: dict, part: str) -> int:
    return sum(part in t.split("_") for t in layer_types(cfg))


def model_config(cfg: dict):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    linear = cfg["linear_attn_config"]
    if (cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu"
            or cfg["q_lora_rank"] is not None or not cfg["mla_use_nope"]
            or cfg["rope_scaling"] is not None or not cfg["moe_renormalize"]
            or cfg["moe_router_activation_func"] != "sigmoid"
            or cfg["num_expert_group"] != 1 or cfg["topk_group"] != 1
            or cfg["num_shared_experts"] != 1 or cfg["moe_layer_freq"] != 1
            or cfg["num_nextn_predict_layers"] != 0
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]
            or linear["num_heads"] != cfg["num_attention_heads"]):
        raise ValueError("the kimi_linear family runs Kimi-Linear-48B-A3B's "
                         "layer as published: an untied head, silu, no q "
                         "latent, no positions in the latent layers, "
                         "renormalised sigmoid scores with a selection bias "
                         "and one expert group, one shared expert, an expert "
                         "layer in every layer behind the dense ones, no "
                         "multi-token-prediction module, a key/value head a "
                         "query head, as many KDA heads as attention heads")
    recomputed = cfg["recomputed_layer_types"]

    def kind(layer_type):
        mixer, ffn = layer_type.split("_")
        dense = ffn == DENSE
        return (("mixer", "kda" if mixer == KDA else "latent"),
                ("ffn", "swiglu" if dense else "moe"),
                ("d_ff", cfg["intermediate_size"] if dense
                 else cfg["moe_intermediate_size"]),
                ("layer_remat", layer_type in recomputed))

    return TransformerConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["moe_intermediate_size"],
        max_seq=cfg["model_max_length"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        positions="none",
        norm_eps=cfg["rms_norm_eps"],
        ffn="moe", n_experts=cfg["published"]["num_experts"],
        top_k=cfg["num_experts_per_token"],
        tied_head=False,
        attn_core=cfg["attention_core"],
        flash_blocks=tuple(cfg["flash_blocks"]),
        flash_interpret=bool(cfg.get("flash_interpret", False)),
        mixer="kda",
        kda_heads=(linear["num_heads"], linear["head_dim"]),
        conv_taps=linear["short_conv_kernel_size"],
        latent_dims=(0, cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"]),
        router_scores="sigmoid", router_bias=True, gates="renorm",
        routed_scale=float(cfg["routed_scaling_factor"]),
        experts_held=(cfg["first_expert_held"], cfg["num_experts"]),
        shared_ff=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
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
    every expert layer's router matrix."""
    return {**tree, "layers": tuple(
        {**stack, "router": of(stack["router"])} if "router" in stack else stack
        for stack in tree["layers"])}


def loss_fn(cfg: dict):
    """The model's loss. Where the configuration says `routers_trained`
    false the routers' matrices are constants of it, for the Qwen3-Next
    family's reason (`families.qwen3_next.loss_fn`; PERF.md, PR 36): one
    chip's share of the experts gives a router only the part of its gradient
    that comes through the experts held. The selection bias is a constant of
    the loss by what it is."""
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


def _hyper(cfg: dict) -> dict:
    return dict(kda_head_dim=cfg["linear_attn_config"]["head_dim"],
                heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
                pe=cfg["qk_rope_head_dim"], value=cfg["v_head_dim"],
                kv_rank=cfg["kv_lora_rank"], eps=cfg["rms_norm_eps"],
                top_k=cfg["num_experts_per_token"],
                routed_scale=float(cfg["routed_scaling_factor"]),
                first_held=cfg["first_expert_held"],
                query_block=cfg.get("reference_query_block", REFERENCE_QUERY_BLOCK),
                position_block=cfg.get("reference_position_block",
                                       REFERENCE_POSITION_BLOCK),
                head_block=REFERENCE_HEAD_BLOCK)


def reference_loss_and_grads(cfg: dict, state, batch):
    """The reference's loss and gradients, the routers' set to zero where
    the configuration does not train them (`loss_fn`)."""
    import jax.numpy as jnp

    from benchmark.reference import kimi_linear as ref

    loss, grads = ref.loss_and_grads(state, batch, **_hyper(cfg))
    if not cfg["routers_trained"]:
        grads = _with_routers(grads, jnp.zeros_like)
    return loss, grads


def routing_stats(cfg: dict, state, batch) -> dict:
    """The program's routing counters on one host batch, as plain numbers,
    an entry an expert layer: token-choices computed per held expert,
    `held_rows` their sum, `dropped` (0 by construction), the busiest held
    expert's load over the mean of all 256, and `bias_moved`, the
    token-choices the selection bias changed. Outside the step: the step
    returns a loss and nothing else."""
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

    from benchmark.reference import kimi_linear as ref
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
    return (cfg["num_experts_per_token"] * cfg["num_experts"]
            / cfg["published"]["num_experts"])


def kda_core_flops_per_sample(cfg: dict) -> float:
    """The delta rule of one KDA layer over one sequence, as the recurrence
    states it, a head and position: the decay of the state's rows (dk x dv
    multiplies), S'^T k, the rank-one update k u^T and S^T q (2 dk dv each):
    7 dk dv operations forward, twice that backward. The chunked form the
    program runs does more (the in-chunk products, the pairs' decays feature
    by feature and the triangular system); what a kernel need not do is not
    counted, whatever kernel implements the rule."""
    linear = cfg["linear_attn_config"]
    return (3 * 7.0 * linear["head_dim"] ** 2 * linear["num_heads"]
            * cfg["sequence_length"])


def kda_core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """What the rule must move for one layer and sequence: forward reads q,
    k, v, g, beta and writes o; backward reads q, k, v, g, beta, do and
    writes dq, dk, dv, dg, dbeta: 11 arrays of a head's features in the
    model's type (q, k twice and dq, dk; v twice, o, do, dv), 3 of a float32
    a key feature (g twice and dg) and 3 of a float32 a head (beta). The
    chunk-boundary states, T and P that the program keeps between its passes
    are its own choice and not counted."""
    linear = cfg["linear_attn_config"]
    width = linear["num_heads"] * linear["head_dim"]
    return cfg["sequence_length"] * (
        11.0 * width * itemsize + 3.0 * width * 4 + 3.0 * linear["num_heads"] * 4)


def mla_core_flops_per_sample(cfg: dict) -> float:
    """The softmax core of one latent-attention layer over one sequence, the
    causal half: forward 2 matmuls (QK^T over 128 + 64 features, PV over
    128), backward 4 (dV, dP, dQ, dK), each 2 operations a seen pair and
    feature, over the heads. What the two-pass backward recomputes is not
    counted. The counts are the layer's, whatever implements the core."""
    s = cfg["sequence_length"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (3 * 2.0 * (s * s / 2) * cfg["num_attention_heads"]
            * (qk + cfg["v_head_dim"]))


def mla_core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv: q, k, dq, dk, twice each but the cotangents (6
    arrays), at the q/k head size, and v, o, do, dv likewise (6) at the
    value head size, of S positions and all heads each. (A core that read
    the one shared key unlaid would move less; the layer as published lays
    k out a head.)"""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (6.0 * (qk + cfg["v_head_dim"]) * cfg["num_attention_heads"]
            * cfg["sequence_length"] * itemsize)


def mixer_params_per_token(cfg: dict, mixer: str) -> float:
    """Parameters of one mixer that multiply every token. KDA: W_q, W_k,
    W_v, their taps, the decay's and the gate's two low-rank halves, W_beta,
    W_o. Latent: W_q, W_kv_down (the shared key's columns among them),
    W_kv_up, W_o. Norms' scales, A_log and dt_bias do no matmul."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if mixer == KDA:
        linear = cfg["linear_attn_config"]
        rank, width = linear["head_dim"], linear["num_heads"] * linear["head_dim"]
        return (3 * d * width + 3 * linear["short_conv_kernel_size"] * width
                + 2 * (d * rank + rank * width) + d * linear["num_heads"]
                + width * d)
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * h * qk + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def router_params_per_token(cfg: dict) -> float:
    """A layer's router, over all published experts."""
    return cfg["hidden_size"] * cfg["published"]["num_experts"]


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters that multiply every token: each layer's mixer; the dense
    feed-forward, or the router over all published experts, the shared
    expert and the expected share of a token's routed-expert passes; the
    untied head over the rows held. Embedding lookups and the norms' scales
    do no matmul."""
    d = cfg["hidden_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    feed_forward = {DENSE: 3 * d * cfg["intermediate_size"],
                    SPARSE: (router_params_per_token(cfg)
                             + cfg["num_shared_experts"] * expert
                             + expected_expert_passes(cfg) * expert)}
    return cfg["vocab_size"] * d + sum(
        mixer_params_per_token(cfg, t.split("_")[0]) + feed_forward[t.split("_")[1]]
        for t in layer_types(cfg))


def flops_per_sample(cfg: dict) -> float:
    """Operations the forward and backward passes require for one sequence
    of `sequence_length` tokens: projections, convolutions, routers, shared
    experts, the dense layer, the held experts' expected share, the untied
    head, the delta rule of the KDA layers and the causal half of the latent
    ones; no recomputation. A router that is not trained has no
    weight-gradient product: one of its three passes is not required."""
    idle = 0 if cfg["routers_trained"] else (
        _count(cfg, SPARSE) * router_params_per_token(cfg))
    return (2 * (3.0 * matmul_params_per_token(cfg) - idle) * cfg["sequence_length"]
            + _count(cfg, KDA) * kda_core_flops_per_sample(cfg)
            + _count(cfg, MLA) * mla_core_flops_per_sample(cfg))


# -- the per-layer metrics' common part -------------------------------------

CORE_SCOPES = {KDA: "kda_core", MLA: "attn_latent"}
MIXER_SCOPES = {KDA: "kda", MLA: "attn"}
_CORE_COUNTS = {KDA: (kda_core_flops_per_sample, kda_core_bytes_per_sample),
                MLA: (mla_core_flops_per_sample, mla_core_bytes_per_sample)}


def core_ms(record, trace, mixer: str):
    """Own time a step of the device ops under `kda_core` (the rule's four
    kernels, the triangular inverse and the running sums between them,
    forward and backward) or `attn_latent` (the flash forward kernel, the
    two backward kernels and the row sums between them)."""
    return scope_own_ms(record, trace, {CORE_SCOPES[mixer]})


def core_roofline_pct(record, trace, mixer: str):
    """The least time the chip could take for the cores of the layers of one
    mixer, the larger of their required operations over the bf16 peak and
    their required bytes over the memory peak (`peaks.json`), over the time
    they took, in %. None where there is no time to divide by."""
    from benchmark.harness import load_peaks

    ms = core_ms(record, trace, mixer)
    if not ms:
        return None if ms is None else 0.0
    cfg = cell_config(record)
    peaks = load_peaks(record["device"]["kind"])
    flops, moved = _CORE_COUNTS[mixer]
    n = record["samples_per_step"] * _count(cfg, mixer)
    roof_s = max(n * flops(cfg) / peaks["bf16_flops"],
                 n * moved(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * roof_s / (ms * 1e-3)


def mixer_ms(record, trace, mixer: str):
    """Own time a step under the mixer's scope (`kda`, `attn`) that is not
    its cores': the norm before the mixer, the projections, convolutions,
    norms and gates, forward and backward."""
    whole = scope_own_ms(record, trace, {MIXER_SCOPES[mixer], CORE_SCOPES[mixer]})
    core = core_ms(record, trace, mixer)
    if whole is None or core is None:
        return None
    return whole - core
