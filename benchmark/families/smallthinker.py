"""The SmallThinker family (SmallThinker-21BA3B-Instruct's language model):
kungfu_tpu.models.transformer under a configuration file whose keys are the
source's (a Hugging Face `config.json` of `model_name`
smallthinker_21b_instruct): 28 query heads on 4 key/value heads of 128 with no
q/k norm; a layer a full-attention layer without any position signal or a
window-4,096 layer with rotary positions (`sliding_window_layout`,
`rope_layout`, which agree: one full layer, then three window layers); every
layer an expert layer of softmax scores over 64 relu-gated experts of width
768, 6 a token renormalised, **routed from the layer's own input, ahead of the
mixer**, of which this chip holds a share; an untied head over a slice of the
vocabulary. The system under test is imported; the operation and byte counts,
the batches and the plain reference are the benchmark's own.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.olmoe import EXPERT_KERNELS, cell_config, scope_own_ms

REFERENCE_SAMPLES = 1  # one sequence_length-token sequence (S + 1 ids)

FULL, WINDOW = "full_attention", "sliding_attention"  # `recomputed_layer_types`

# The program computes in bfloat16 and the reference in float32; the router
# (on the bfloat16 residual stream in the program, the float32 one in the
# reference), the norms' statistics, head and loss are float32 in both. Each
# tolerance is set from two readings on the chip at the published widths,
# 16,384 positions and the initial parameters (my chip runs, PR 65, call 2,
# seeds 2305843013 and 77; PERF.md section 6): the error of the program, and
# the error of the same program with every matrix rounded to float8_e4m3 (3
# mantissa bits, the nearest precision below bfloat16), which has to fail.
# Gradients, as one vector |g - g_ref| / |g_ref|: 0.01727 and 0.01790 (and
# 0.016 to 0.019 over the seeds of the cell's own runs); with 8-bit operands
# 0.1352 and 0.1369. GRAD_RTOL is 2.2 times the largest reading and 0.30 of
# the 8-bit one. The fault programs read far over it on both seeds: the router
# fed the normed state behind the mixer 0.443 and 0.348, a silu gate 0.147 and
# 0.148, rotary positions on the full layer 0.155 and 0.164, the window left
# off 0.597 and 0.653, gates not renormalised 0.365 and 0.358.
# The loss: 5.8e-6 and 6.3e-6 of itself, in 8 bits 7.3e-5 and 4.3e-5 (the
# logits are small at the initial parameters): LOSS_RTOL is the other
# transformer cells', thirty times the larger reading; the loss cannot see
# 8-bit operands and the gradients decide, as for the other families.
# The router's choice is discrete: the program's layer input is a bfloat16 and
# the reference's a float32, so a token whose 6th and 7th scores differ by
# less than that rounding moves them takes another 6th expert;
# `differing_choices` counts them (3,795 and 2,969 of 393,216 token-choices,
# 0.97 and 0.76 %), and they are in the readings. For the same reason a router
# whose product alone runs on bfloat16 operands cannot be told from the
# program by these limits at the initial parameters (0.01749 and 0.01912: its
# input is a bfloat16 already); PERF.md section 6 has what a router in
# bfloat16 throughout reads.
LOSS_RTOL = 2e-4
GRAD_RTOL = 4e-2

# the reference's blocks, where the configuration's file names none (the
# tests' do, so that a sequence of theirs is several blocks too)
REFERENCE_QUERY_BLOCK = 128  # 28 heads x 128 x 16,384 float32 scores: 0.23 GB
REFERENCE_POSITION_BLOCK = 1024  # x 18,992 float32 logits: 0.08 GB


def layers_of(cfg: dict) -> list:
    """(rotary positions or none, the window or 0, the layer's kind's name) a
    layer run here, from the two layouts."""
    n = cfg["num_hidden_layers"]
    return [(bool(rope), cfg["sliding_window_size"] if band else 0,
             WINDOW if band else FULL)
            for rope, band in zip(cfg["rope_layout"][:n],
                                  cfg["sliding_window_layout"][:n], strict=True)]


def model_config(cfg: dict):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    if (cfg["tie_word_embeddings"] or not cfg["norm_topk_prob"]
            or not cfg["moe_primary_router_apply_softmax"]
            or cfg["rope_scaling"] is not None
            or cfg["router_input"] != "layer_input"
            or len(cfg["rope_layout"]) != cfg["num_hidden_layers"]
            or len(cfg["sliding_window_layout"]) != cfg["num_hidden_layers"]):
        raise ValueError("the smallthinker family runs SmallThinker-21BA3B's "
                         "layers as published: softmax scores renormalised over "
                         "the chosen, routed from the layer's input, plain "
                         "rotary positions where a layer has them, an untied "
                         "head, a layout entry a layer")
    recomputed = cfg["recomputed_layer_types"]
    kinds = tuple((("positions", "rope" if rope else "none"), ("window", window),
                   ("layer_remat", name in recomputed))
                  for rope, window, name in layers_of(cfg))
    return TransformerConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["moe_ffn_hidden_size"],
        max_seq=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        positions="none", rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        ffn="moe", n_experts=cfg["published"]["moe_num_primary_experts"],
        top_k=cfg["moe_num_active_primary_experts"], gates="renorm",
        expert_act="reglu", router_input="layer",
        tied_head=False,
        attn_core=cfg["attention_core"],
        flash_blocks=tuple(cfg["flash_blocks"]),
        flash_interpret=bool(cfg.get("flash_interpret", False)),
        head_size=cfg["head_dim"], n_kv_heads=cfg["num_key_value_heads"],
        experts_held=(cfg["first_expert_held"], cfg["moe_num_primary_experts"]),
        layer_kinds=kinds,
    )


def init(cfg: dict, seed: int):
    """The train state (the parameter tree), made on the device in one
    jitted call from the seed."""
    import jax

    from kungfu_tpu.models.transformer import init_transformer

    mc = model_config(cfg)
    return jax.jit(lambda key: init_transformer(key, mc))(jax.random.PRNGKey(seed))


def _held(cfg: dict, params):
    """`params` as the loss reads them: the routers' matrices constants of it
    where the configuration does not train them."""
    import jax

    if cfg["routers_trained"]:
        return params
    return {**params, "layers": tuple(
        {**stack, "router": jax.lax.stop_gradient(stack["router"])}
        for stack in params["layers"])}


def loss_fn(cfg: dict):
    """The model's loss, the next-token cross-entropy. Where the
    configuration says `routers_trained` false the routers' matrices are
    constants of it, for the Qwen3-Next family's reason
    (`families.qwen3_next.loss_fn`; PERF.md, PR 36): one chip's share of the
    experts gives a router only the part of its gradient that comes through
    the experts held. The gates' derivative still reaches the residual
    stream at the layer's input."""
    from kungfu_tpu.models.transformer import transformer_loss

    mc = model_config(cfg)
    return lambda params, batch: transformer_loss(_held(cfg, params), batch, mc)


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
    return dict(layers=tuple((rope, window) for rope, window, _ in layers_of(cfg)),
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
                rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
                top_k=cfg["moe_num_active_primary_experts"],
                first_held=cfg["first_expert_held"],
                routers_trained=bool(cfg["routers_trained"]),
                query_block=cfg.get("reference_query_block",
                                    REFERENCE_QUERY_BLOCK),
                position_block=cfg.get("reference_position_block",
                                       REFERENCE_POSITION_BLOCK))


def reference_loss_and_grads(cfg: dict, state, batch):
    """The reference's loss and gradients, the routers' zero where the
    configuration does not train them (the reference takes their matrices as
    constants)."""
    from benchmark.reference import smallthinker as ref

    (loss, _), grads = ref.loss_and_grads(state, batch, **_hyper(cfg))
    return loss, grads


def routing_stats(cfg: dict, state, batch) -> dict:
    """The program's routing counters on one host batch, as plain numbers, an
    entry a layer: token-choices computed per held expert, `held_rows` their
    sum, `dropped` (0 by construction) and the busiest held expert's load
    over the mean of all 64. Outside the step: the step returns a loss and
    nothing else."""
    import jax

    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    stats = jax.jit(lambda p, t: transformer.routing_stats(p, t, mc))(
        state, batch[:, :-1])
    return {k: np.asarray(v).tolist() for k, v in stats.items() if k != "chosen"}


def gate_zero_shares(cfg: dict, state, batch) -> list:
    """The share of the held rows' relu(W_gate m) that is exactly 0, a layer:
    what the model's sparse ReGLU leaves for a kernel to skip
    (`transformer.gate_zero_shares`, the gauge `kungfu_moe_gate_zero_share`).
    Outside the step, as `routing_stats`."""
    import jax

    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    return np.asarray(jax.jit(lambda p, t: transformer.gate_zero_shares(
        p, t, mc))(state, batch[:, :-1])).tolist()


def differing_choices(cfg: dict, state, batch) -> int:
    """Token-choices of the program's routers that the reference's routers
    do not make for the same token, over all layers."""
    import jax

    from benchmark.reference import smallthinker as ref
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
    return (cfg["moe_num_active_primary_experts"] * cfg["moe_num_primary_experts"]
            / cfg["published"]["moe_num_primary_experts"])


def seen_pairs(cfg: dict, window: int) -> float:
    """Query-key pairs a head's mask lets through over one sequence: the
    causal half S^2 / 2, or the band S x window - window^2 / 2."""
    s = cfg["sequence_length"]
    window = min(window, s)
    return s * window - window * window / 2 if window else s * s / 2


def core_flops_per_sample(cfg: dict, window: int) -> float:
    """The attention core of one layer over one sequence: forward 2 matmuls
    (QK^T, PV), backward 4 (dV, dP, dQ, dK), each 2 operations a seen pair
    and feature, over the query heads. What the two-pass backward recomputes
    (QK^T twice more, dP once more) is not counted, as `mfu_pct` does not."""
    return (6 * 2.0 * seen_pairs(cfg, window) * cfg["num_attention_heads"]
            * cfg["head_dim"])


def core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """What the core must move for one layer and sequence: forward reads q,
    k, v and writes o; backward reads q, k, v, o, do and writes dq, dk, dv:
    12 arrays of S x heads x head size, q, o, do, dq at the query heads (6)
    and k, v, dk, dv at the key/value heads (6)."""
    return (6.0 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])
            * cfg["sequence_length"] * cfg["head_dim"] * itemsize)


def mixer_params_per_token(cfg: dict) -> float:
    """W_q, W_k, W_v, W_o: parameters of a mixer that multiply every token.
    The norms do no matmul."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return (2 * d * cfg["num_attention_heads"] * hd
            + 2 * d * cfg["num_key_value_heads"] * hd)


def router_params_per_token(cfg: dict) -> float:
    """A layer's router, over all published experts."""
    return cfg["hidden_size"] * cfg["published"]["moe_num_primary_experts"]


def expert_params_per_token(cfg: dict) -> float:
    """The expected share of a token's routed-expert passes (no shared
    expert), three matrices an expert: the rows of the share's chunk that
    belong to no group are not counted."""
    return (expected_expert_passes(cfg) * 3 * cfg["hidden_size"]
            * cfg["moe_ffn_hidden_size"])


def flops_per_sample(cfg: dict) -> float:
    """Operations the forward and backward passes require for one sequence
    of `sequence_length` tokens: the four projections, the held experts'
    expected 1.5 passes a token and the untied head's product over the rows
    held, three passes each; the routers, two where they are not trained
    (forward and the gates' derivative in the layer's input); the causal half
    of the full cores and the band of the window ones; no recomputation, and
    nothing for the rows of the share's chunk that came to no held expert."""
    s = cfg["sequence_length"]
    router_passes = 3 if cfg["routers_trained"] else 2
    per_token = 3.0 * cfg["vocab_size"] * cfg["hidden_size"]
    cores = 0.0
    for _, window, _ in layers_of(cfg):
        per_token += (3.0 * (mixer_params_per_token(cfg)
                             + expert_params_per_token(cfg))
                      + router_passes * router_params_per_token(cfg))
        cores += core_flops_per_sample(cfg, window)
    return 2 * per_token * s + cores


# -- the per-layer metrics' common part -------------------------------------

CORE_SCOPES = {"window": "attn_window", "full": "attn_full"}
ROUTING_SCOPES = {"moe_early_router", "moe_plan"}


def core_ms(record, trace, which: str):
    """Own time a step of the device ops under `attn_window` or `attn_full`:
    the flash forward kernel, the two backward kernels, the row sums between
    them and the layout copies at their doors, of the window layers or of the
    full ones."""
    return scope_own_ms(record, trace, {CORE_SCOPES[which]})


def core_roofline_pct(record, trace, which: str):
    """The least time the chip could take for the window or the full layers'
    cores, the larger of their required operations over the bf16 peak and
    their required bytes over the memory peak (`peaks.json`), over the time
    they took, in %. None where there is no time to divide by, and of a
    record of another family's configuration."""
    from benchmark.harness import load_peaks

    ms = core_ms(record, trace, which)
    if not ms:
        return None if ms is None else 0.0
    cfg = cell_config(record)
    if cfg.get("family") != "smallthinker":
        return None
    peaks = load_peaks(record["device"]["kind"])
    n = record["samples_per_step"]
    roof_s = sum(max(n * core_flops_per_sample(cfg, window) / peaks["bf16_flops"],
                     n * core_bytes_per_sample(cfg) / peaks["hbm_bytes_per_s"])
                 for _, window, _ in layers_of(cfg)
                 if bool(window) == (which == "window"))
    return 100.0 * roof_s / (ms * 1e-3)


def early_router_ms(record, trace):
    """Own time a step under `moe_early_router` and `moe_plan`: the router's
    float32 product on the layer's input, its softmax and top-k (twice a
    step in a layer that is run again, and the gates' derivative), and the
    stable sort of the token-choices with their count (once: the order is
    kept)."""
    return scope_own_ms(record, trace, ROUTING_SCOPES)


def attn_proj_ms(record, trace):
    """Own time a step under `attn` that is not the cores': the norm before
    the mixer, the four projections and the rotary pass of the window
    layers."""
    whole = scope_own_ms(record, trace, {"attn"})
    cores = [core_ms(record, trace, which) for which in CORE_SCOPES]
    if whole is None or None in cores:
        return None
    return whole - sum(cores)


def moe_ms(record, trace):
    """Own time a step under `moe`: the routing ahead of the mixer, the norm
    before the experts, dispatch, the held relu-gated experts and combine;
    the grouped-matmul kernels claimed by their name."""
    return scope_own_ms(record, trace, {"moe"}, EXPERT_KERNELS)
