"""The Nemotron-H family (NVIDIA-Nemotron-3-Nano-30B-A3B): kungfu_tpu.models.
transformer under a configuration file whose keys are the source's (a Hugging
Face `config.json` of `model_type` nemotron_h): layers that are one residual
branch each by the letter of `hybrid_override_pattern`, `M` a Mamba-2 mixer
(64 heads of 64, a state of 128, 8 groups, a convolution of 4 taps with a
bias, a norm over groups behind the gate), `*` softmax attention of 32 query
heads on 2 key/value heads of 128 with no position signal, `E` an expert layer
(sigmoid scores under a selection bias, the chosen renormalised and scaled,
128 routed two-matrix relu^2 experts of which this chip holds a share beside a
shared expert), an untied head over a slice of the vocabulary. The system
under test is imported; the operation and byte counts, the batches and the
plain reference are the benchmark's own.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.olmoe import (EXPERT_KERNELS, cell_config,
                                      scope_own_ms)

REFERENCE_SAMPLES = 1  # one sequence_length-token sequence

# The program computes in bfloat16 and the reference in float32; router, step,
# decay, the scan's state, head and loss are float32 in both. Each tolerance
# is set from two readings on the chip at the published widths, 8,192 tokens
# and the initial parameters (my chip runs, PR 43; PERF.md section 6): the
# largest error of the program over its seeds, and the error of the same
# program with every matrix rounded to float8_e4m3 (3 mantissa bits, the
# nearest precision below bfloat16), which has to fail.
# Gradients, as one vector |g - g_ref| / |g_ref|: 0.0417 to 0.0535 over 33
# runs (median 0.0488); 0.2624 in 8 bits. The limit stands between, 1.5 times
# the largest reading and a third of the 8-bit one. The leaves that
# weigh (embedding, the first Mamba-2 layer's projections, the first shared
# expert, the head) read 0.032 to 0.048; the held experts' matrices read 0.10
# to 0.19 and weigh a tenth as much.
# The loss: 1.2e-6 to 1.13e-4 over those runs (2.8e-5 the first, 4.1e-5 the
# median), 1.46e-4 in 8 bits: the precision hardly moves it, so the limit is
# the other transformer cells' (seven times the first reading), and the 8-bit
# program is refused by its gradients and not by its loss.
# The router's choice is discrete: the program's normed token is a bfloat16
# and the reference's a float32, so a token whose 6th and 7th biased scores
# differ by less than that rounding takes another 6th expert;
# `differing_choices` counts them (2,415 to 2,505 of a sample's 196,608), and
# they are in the readings. The gate after the norm, a norm over all 4,096
# features, B and C of the wrong group, no D x, no softplus, a rotary pass,
# gated silu or relu in the experts, the bias in the weight, a scale of 1 read
# over twice `GRAD_RTOL` on a state in which they weigh
# (tests/test_nemotron_h_faults.py).
LOSS_RTOL = 2e-4
GRAD_RTOL = 8e-2

REFERENCE_QUERY_BLOCK = 256  # 32 heads x 256 x 8,192 float32 scores: 0.27 GB
REFERENCE_POSITION_BLOCK = 128  # 64 heads' (64, 128) states of a block: 0.27 GB

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# the names a configuration's `recomputed_layer_types` uses
LAYER_NAMES = {MAMBA: "mamba", EXPERTS: "moe", ATTENTION: "attention"}


def layer_types(cfg: dict) -> list:
    """The kind of each layer run here, a letter of the pattern each."""
    kinds = list(cfg["hybrid_override_pattern"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set(LAYER_NAMES):
        raise ValueError(f"hybrid_override_pattern {cfg['hybrid_override_pattern']!r} "
                         f"is not {cfg['num_hidden_layers']} letters of M, E and *")
    return kinds


def model_config(cfg: dict):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    if (cfg["tie_word_embeddings"] or cfg["attention_bias"] or cfg["mlp_bias"]
            or cfg["use_bias"] or cfg["mamba_proj_bias"]
            or not cfg["use_conv_bias"] or cfg["mlp_hidden_act"] != "relu2"
            or cfg["mamba_hidden_act"] != "silu" or not cfg["norm_topk_prob"]
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1
            or cfg["n_shared_experts"] != 1 or cfg["sliding_window"] is not None
            or cfg["norm_eps"] != cfg["layer_norm_epsilon"]):
        raise ValueError("the nemotron_h family runs Nemotron-3-Nano's layers "
                         "as published: an untied head, no bias but the "
                         "convolution's, relu2 experts, a silu in the mixer, "
                         "renormalised sigmoid scores with no expert groups, "
                         "one shared expert, no window")
    recomputed = cfg["recomputed_layer_types"]
    # every layer is one branch: what the other would be is "none"
    branch = {MAMBA: (("mixer", "mamba2"), ("ffn", "none")),
              ATTENTION: (("mixer", "attention"), ("ffn", "none")),
              EXPERTS: (("mixer", "none"), ("ffn", "moe"))}

    def kind(letter):
        return branch[letter] + (("layer_remat", LAYER_NAMES[letter] in recomputed),)

    return TransformerConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["moe_intermediate_size"],
        max_seq=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        positions="none",
        norm_eps=cfg["layer_norm_epsilon"],
        ffn="moe", n_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        tied_head=False,
        attn_core=cfg["attention_core"],
        flash_blocks=tuple(cfg["flash_blocks"]),
        flash_interpret=bool(cfg.get("flash_interpret", False)),
        head_size=cfg["head_dim"], n_kv_heads=cfg["num_key_value_heads"],
        mixer="mamba2",
        ssm_dims=(cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                  cfg["ssm_state_size"], cfg["n_groups"]),
        conv_taps=cfg["conv_kernel"],
        router_scores="sigmoid", router_bias=True, gates="renorm",
        routed_scale=float(cfg["routed_scaling_factor"]),
        expert_act="relu2",
        experts_held=(cfg["first_expert_held"], cfg["n_routed_experts"]),
        shared_ff=(cfg["n_shared_experts"]
                   * cfg["moe_shared_expert_intermediate_size"]),
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
    return dict(layers=layer_types(cfg),
                ssm_heads=cfg["mamba_num_heads"],
                ssm_head_dim=cfg["mamba_head_dim"],
                ssm_state=cfg["ssm_state_size"], ssm_groups=cfg["n_groups"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
                eps=cfg["layer_norm_epsilon"], top_k=cfg["num_experts_per_tok"],
                routed_scale=float(cfg["routed_scaling_factor"]),
                first_held=cfg["first_expert_held"],
                query_block=REFERENCE_QUERY_BLOCK,
                position_block=REFERENCE_POSITION_BLOCK)


def reference_loss_and_grads(cfg: dict, state, batch):
    """The reference's loss and gradients, the routers' set to zero where
    the configuration does not train them (`loss_fn`)."""
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h as ref

    loss, grads = ref.loss_and_grads(state, batch, **_hyper(cfg))
    if not cfg["routers_trained"]:
        grads = _with_routers(grads, jnp.zeros_like)
    return loss, grads


def routing_stats(cfg: dict, state, batch) -> dict:
    """The program's routing counters on one host batch, as plain numbers,
    an entry an expert layer: token-choices computed per held expert,
    `held_rows` their sum, `dropped` (0 by construction), the busiest held
    expert's load over the mean of all 128, `bias_moved`, the token-choices
    the selection bias changed, and `layer`, which of the model's layers each
    entry is. Outside the step: the step returns a loss and nothing else."""
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

    from benchmark.reference import nemotron_h as ref
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
    document of S + 1 tokens (no packing, no boundary mask, no state reset);
    the loss shifts them by one. Ids are uniform over the rows of the
    vocabulary held here: over a share of the experts the step's work is the
    token-choices that land on the experts held, and a random router is
    balanced only over diverse inputs (PERF.md, PR 33)."""
    rng = np.random.default_rng([seed, i])
    return rng.integers(0, cfg["vocab_size"],
                        (n, cfg["sequence_length"] + 1), dtype=np.int32)


# -- operation and byte counts (2 a multiply-add; backward twice the forward;
#    nothing that is recomputed is counted) ----------------------------------


def expected_expert_passes(cfg: dict) -> float:
    """Routed-expert passes a token that fall on the experts held here, in
    expectation under a balanced router: top_k x held / published."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["published"]["n_routed_experts"])


def ssm_inner(cfg: dict) -> int:
    """The Mamba-2 mixer's inner width: its heads' product, not `expand` x
    hidden size."""
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def ssm_core_flops_per_sample(cfg: dict) -> float:
    """The state-space scan of one layer over one sequence, as the chunked
    form at the configuration's `chunk_size` C states it, whatever
    implements it: a position of a group the scores C B^T against its
    chunk (2 C N), a position of a head their product with x (2 C P), the
    chunk's state B^T x and its read-out C S (2 N P each); forward once,
    backward twice. (The recurrence a position at a time states 5 N P a head
    and position, the chunked form 2 C P + 4 N P = 6 N P at these sizes and
    the scores beside them: the form every implementation on a matrix unit
    runs is the one counted, as ISSUE 43 fixed it; the bytes bound the scan
    either way.)"""
    C, N, P = cfg["chunk_size"], cfg["ssm_state_size"], cfg["mamba_head_dim"]
    a_position = (cfg["n_groups"] * 2.0 * C * N
                  + cfg["mamba_num_heads"] * (2.0 * C * P + 4.0 * N * P))
    return 3 * a_position * cfg["sequence_length"]


def ssm_core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """What the scan must move for one layer and sequence: forward reads x,
    B, C, Delta and writes y; backward reads x, B, C, Delta, dy and writes
    dx, dB, dC, dDelta: 5 arrays at the heads' width, 6 at a group's state
    size a group, and 3 of a float32 a head and position. The chunk-boundary
    states the program keeps between its passes are its own choice and not
    counted."""
    return cfg["sequence_length"] * (
        5.0 * ssm_inner(cfg) * itemsize
        + 6.0 * cfg["n_groups"] * cfg["ssm_state_size"] * itemsize
        + 3.0 * cfg["mamba_num_heads"] * 4)


def attn_core_flops_per_sample(cfg: dict) -> float:
    """The softmax core of one attention layer over one sequence, the causal
    half: forward 2 matmuls (QK^T, PV), backward 4 (dV, dP, dQ, dK), each 2
    operations a seen pair and feature, over the query heads. What the
    two-pass backward recomputes is not counted."""
    s = cfg["sequence_length"]
    return 6 * 2.0 * (s * s / 2) * cfg["num_attention_heads"] * cfg["head_dim"]


def attn_core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv: 6 arrays at the query heads and 6 at the key/value
    heads, of S x head size."""
    return (6.0 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])
            * cfg["sequence_length"] * cfg["head_dim"] * itemsize)


def router_params_per_token(cfg: dict) -> float:
    """A layer's router, over all published experts."""
    return cfg["hidden_size"] * cfg["published"]["n_routed_experts"]


def layer_params_per_token(cfg: dict, kind: str) -> float:
    """Parameters of one layer that multiply every token. Mamba-2: W_in, the
    convolution's taps over the x, B, C channels, W_out. Attention: W_q,
    W_k, W_v, W_o. Expert layer: the router over all published experts, the
    shared expert's two matrices and the expected share of a token's
    routed-expert passes, two matrices each."""
    d = cfg["hidden_size"]
    if kind == MAMBA:
        inner = ssm_inner(cfg)
        conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
        return (d * (inner + conv + cfg["mamba_num_heads"])
                + cfg["conv_kernel"] * conv + inner * d)
    if kind == ATTENTION:
        q_width = cfg["num_attention_heads"] * cfg["head_dim"]
        kv_width = cfg["num_key_value_heads"] * cfg["head_dim"]
        return 2 * d * q_width + 2 * d * kv_width
    return (router_params_per_token(cfg)
            + 2 * d * cfg["n_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]
            + expected_expert_passes(cfg) * 2 * d * cfg["moe_intermediate_size"])


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters that multiply every token: every layer's, and the untied
    head over the rows held. Embedding lookups, the norms' scales and the
    numbers a head (dt_bias, A_log, D) do no matmul."""
    return (cfg["vocab_size"] * cfg["hidden_size"]
            + sum(layer_params_per_token(cfg, t) for t in layer_types(cfg)))


def flops_per_sample(cfg: dict) -> float:
    """Operations the forward and backward passes require for one sequence
    of `sequence_length` tokens: projections, convolutions, routers, shared
    experts, the held experts' expected share, the untied head, the scan of
    the Mamba-2 layers and the causal half of the attention layers; no
    recomputation. A router that is not trained has no weight-gradient
    product: one of its three passes is not required."""
    kinds = layer_types(cfg)
    idle = 0 if cfg["routers_trained"] else (
        kinds.count(EXPERTS) * router_params_per_token(cfg))
    return (2 * (3.0 * matmul_params_per_token(cfg) - idle) * cfg["sequence_length"]
            + kinds.count(MAMBA) * ssm_core_flops_per_sample(cfg)
            + kinds.count(ATTENTION) * attn_core_flops_per_sample(cfg))


# -- the per-layer metrics' common part -------------------------------------

CORE_SCOPES = {MAMBA: "ssm_core", ATTENTION: "attn_full"}
_CORE_COUNTS = {MAMBA: (ssm_core_flops_per_sample, ssm_core_bytes_per_sample),
                ATTENTION: (attn_core_flops_per_sample, attn_core_bytes_per_sample)}


def core_ms(record, trace, kind: str):
    """Own time a step of the device ops under `ssm_core` (the scan's
    kernels, forward and backward, and what adds up a group's dq and dk) or
    `attn_full` (the flash forward kernel, the two backward kernels, the row
    sums between them and the layout copies at their doors)."""
    return scope_own_ms(record, trace, {CORE_SCOPES[kind]})


def core_roofline_pct(record, trace, kind: str):
    """The least time the chip could take for the cores of the layers of one
    kind, the larger of their required operations over the bf16 peak and
    their required bytes over the memory peak (`peaks.json`), over the time
    they took, in %. None where there is no time to divide by."""
    from benchmark.harness import load_peaks

    ms = core_ms(record, trace, kind)
    if not ms:
        return None if ms is None else 0.0
    cfg = cell_config(record)
    peaks = load_peaks(record["device"]["kind"])
    flops, moved = _CORE_COUNTS[kind]
    n = record["samples_per_step"] * layer_types(cfg).count(kind)
    roof_s = max(n * flops(cfg) / peaks["bf16_flops"],
                 n * moved(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * roof_s / (ms * 1e-3)


def mix_ms(record, trace):
    """Own time a step under `ssm` that is not the scan's: `ssm` less
    `ssm_core`."""
    whole = scope_own_ms(record, trace, {"ssm"})
    core = core_ms(record, trace, MAMBA)
    if whole is None or core is None:
        return None
    return whole - core


def moe_ms(record, trace):
    """Own time a step under `moe`, with the grouped-matmul kernels that
    carry no scope (`families.olmoe.EXPERT_KERNELS`)."""
    return scope_own_ms(record, trace, {"moe"}, EXPERT_KERNELS)
