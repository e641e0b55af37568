"""The LFM2-MoE family (LFM2-24B-A2B): kungfu_tpu.models.transformer under a
configuration file whose keys are the source's (a Hugging Face `config.json`
of `model_type` lfm2_moe): by `layer_types` a layer's mixer is a gated short
convolution (`conv`: [B | C | x] = u W_in, 3 taps a channel over B * x, the
gate C, W_out; no activation, no bias, no state) or softmax attention
(`full_attention`: 32 query heads on 8 key/value heads of 64, a q/k norm a
head, rotary positions at 1e6); the first `num_dense_layers` feed-forwards are
dense gated-silu ones of `intermediate_size` and the others expert layers:
sigmoid router scores chosen under a selection bias, the chosen renormalised,
64 routed experts of which this chip holds a share, no shared expert; a tied
head over a slice of the vocabulary. The system under test is imported; the
operation and byte counts, the batches and the plain reference are the
benchmark's own.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.olmoe import cell_config, scope_own_ms

REFERENCE_SAMPLES = 1  # one sequence_length-token sequence (S + 1 ids)

# The program computes in bfloat16 and the reference in float32; the
# convolution's products, the router, the norms' statistics, head and loss are
# float32 in both. Each tolerance is set from two readings on the chip at the
# published widths, 8,192 rows, the initial parameters and the 4,096
# positions that are compared (`compared`; my chip runs, PR 57, call 7;
# PERF.md section 6): the largest error of the program over its seeds, and
# the error of the same program with every matrix rounded to float8_e4m3 (3
# mantissa bits, the nearest precision below bfloat16), which has to fail.
# Gradients, as one vector |g - g_ref| / |g_ref|: 0.02905 to 0.03169 over 14
# readings (median 0.0309); 0.2005 and 0.2012 in 8 bits. The limit stands
# between, 1.58 times the largest reading and a quarter of the 8-bit one.
# Over whole samples of 8,192 positions (calls 1 and 5, before the harness's
# reading of the head made the comparison half the sequence) the same: 0.02990
# to 0.03179 over 17 readings, 0.2007 and 0.2004 in 8 bits.
# Under the OLMoE and GLM cells' 4 to 5 % with two cores of its own: six of
# eight mixers are a convolution of three taps, whose float32 products add
# nothing to the matmuls' rounding.
# The loss: 6.1e-7 to 5.2e-5 over those runs (3.0e-5 the first), 5.6e-5 and
# 8.3e-5 in 8 bits: the precision hardly moves it at the initial parameters,
# so the limit is the other transformer cells' (seven times the first
# reading), and the 8-bit program is refused by its gradients and not by its
# loss.
# The router's choice is discrete: the program's normed token is a bfloat16
# and the reference's a float32, so a token whose 4th and 5th biased scores
# differ by less than that rounding takes another 4th expert;
# `differing_choices` counts them (1,497 and 1,415 of the 98,304
# token-choices of 4,096 positions over the six expert layers, 1.5 %), and
# they are in the readings. A dropped tap, the taps turned round, the two
# gates' thirds exchanged with x's, no gate behind the convolution, no q/k
# norm, no rotary pass, an untied head and the bias in the weight read over
# twice `GRAD_RTOL` on a state in which they weigh
# (tests/test_lfm2_moe_faults.py). The op's products in bfloat16 in the place
# of float32 move a gradient by 0.4 %, which no limit of a cell whose matmuls
# round as much can see: `tests/test_short_conv.py` holds the kernels to the
# float32 products at the op, to a bfloat16 output's last bit.
LOSS_RTOL = 2e-4
GRAD_RTOL = 5e-2

REFERENCE_QUERY_BLOCK = 256  # 32 heads x 256 x 8,192 float32 scores: 0.27 GB
REFERENCE_POSITION_BLOCK = 1024  # x 11,776 float32 gates: 0.05 GB an array

CONV, ATTENTION = "conv", "full_attention"  # `layer_types`' own names
DENSE, SPARSE = "dense", "sparse"  # a layer's feed-forward


def layer_types(cfg: dict) -> list:
    """(mixer, feed-forward) of each layer run here: the first
    `num_hidden_layers` of the published `layer_types`, which the file keeps
    whole, the first `num_dense_layers` of them with a dense feed-forward."""
    mixers = list(cfg["layer_types"][:cfg["num_hidden_layers"]])
    if len(mixers) != cfg["num_hidden_layers"] or set(mixers) - {CONV, ATTENTION}:
        raise ValueError(f"layer_types {cfg['layer_types']!r} do not name "
                         f"{cfg['num_hidden_layers']} layers, conv or "
                         "full_attention")
    return [(mixer, DENSE if l < cfg["num_dense_layers"] else SPARSE)
            for l, mixer in enumerate(mixers)]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def model_config(cfg: dict):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    if (cfg["conv_bias"] or not cfg["norm_topk_prob"]
            or not cfg["use_expert_bias"] or not cfg["tie_word_embeddings"]
            or cfg["rope_parameters"]["rope_type"] != "default"
            or not 0 < cfg["num_dense_layers"] < cfg["num_hidden_layers"]):
        raise ValueError("the lfm2_moe family runs LFM2-24B-A2B's layers as "
                         "published: no bias in the convolution, renormalised "
                         "sigmoid scores under a selection bias, a tied head, "
                         "plain rotary positions, leading dense layers and "
                         "expert layers after them")
    recomputed = cfg["recomputed_layer_types"]

    def kind(mixer, ffn):
        dense = ffn == DENSE
        return (("mixer", "short_conv" if mixer == CONV else "attention"),
                ("ffn", "swiglu" if dense else "moe"),
                ("d_ff", cfg["intermediate_size"] if dense
                 else cfg["moe_intermediate_size"]),
                ("layer_remat", mixer in recomputed or ffn in recomputed))

    return TransformerConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["moe_intermediate_size"],
        max_seq=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        positions="rope",
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        qk_norm=True, norm_eps=cfg["norm_eps"],
        ffn="moe", n_experts=cfg["published"]["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        tied_head=True,
        attn_core=cfg["attention_core"],
        flash_blocks=tuple(cfg["flash_blocks"]),
        flash_interpret=bool(cfg.get("flash_interpret", False)),
        head_size=head_dim(cfg), n_kv_heads=cfg["num_key_value_heads"],
        conv_taps=cfg["conv_L_cache"],
        router_scores="sigmoid", router_bias=True, gates="renorm",
        routed_scale=float(cfg["routed_scaling_factor"]),
        experts_held=(cfg["first_expert_held"], cfg["num_experts"]),
        layer_kinds=tuple(kind(*t) for t in layer_types(cfg)),
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
    """The model's loss. Where the configuration says `routers_trained` false
    the routers' matrices are constants of it, for the Qwen3-Next family's
    reason (`families.qwen3_next.loss_fn`; PERF.md, PR 36): one chip's share
    of the experts gives a router only the part of its gradient that comes
    through the experts held. The selection bias is a constant of the loss by
    what it is."""
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


def compared(cfg: dict, batch):
    """The part of a sample that program and reference are compared on: its
    first `reference_positions` positions where the configuration gives them,
    all of it where not. `harness.precision_faults` finds the head's products
    by a dimension of the head's width, and the cell's slice of the
    vocabulary is as wide as its sequence is long (8,192): over the whole
    sample every bfloat16 product over the positions reads as the head's
    (PERF.md section 7). Half the sequence keeps every kernel's blocks the
    timed step's, with half as many of them."""
    return batch[:, :cfg.get("reference_positions", cfg["sequence_length"]) + 1]


def program_loss_and_grads(cfg: dict):
    """The jitted (state, batch) -> (loss, gradients of `trainable(state)`)
    over `compared(cfg, batch)`, as one device computes them (no mesh): what
    the reference is compared with."""
    import jax

    loss_and_grads = jax.value_and_grad(loss_fn(cfg))
    return jax.jit(lambda state, batch: loss_and_grads(state, compared(cfg, batch)))


def program_losses(cfg: dict, state, batch) -> dict:
    """The program's next-token loss on one host batch (`kungfu_lm_loss`'s
    number), as a plain number. Outside the step."""
    import jax

    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    return {k: float(v) for k, v in jax.jit(
        lambda p, b: transformer.transformer_losses(p, b, mc))(state, batch).items()}


def _hyper(cfg: dict) -> dict:
    return dict(heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
                rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
                eps=cfg["norm_eps"], top_k=cfg["num_experts_per_tok"],
                routed_scale=float(cfg["routed_scaling_factor"]),
                first_held=cfg["first_expert_held"],
                query_block=REFERENCE_QUERY_BLOCK,
                position_block=REFERENCE_POSITION_BLOCK)


def reference_loss_and_grads(cfg: dict, state, batch):
    """The reference's loss and gradients over `compared(cfg, batch)`, the
    routers' set to zero where the configuration does not train them
    (`loss_fn`)."""
    import jax.numpy as jnp

    from benchmark.reference import lfm2_moe as ref

    loss, grads = ref.loss_and_grads(state, compared(cfg, batch), **_hyper(cfg))
    if not cfg["routers_trained"]:
        grads = _with_routers(grads, jnp.zeros_like)
    return loss, grads


def routing_stats(cfg: dict, state, batch) -> dict:
    """The program's routing counters on one host batch, as plain numbers,
    an entry an expert layer: token-choices computed per held expert,
    `held_rows` their sum, `dropped` (0 by construction), the busiest held
    expert's load over the mean of all 64, and `bias_moved`, the
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

    from benchmark.reference import lfm2_moe as ref
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


def conv_core_flops_per_sample(cfg: dict) -> float:
    """The gated convolution of one layer over one sequence, between the two
    projections: a position and channel the product B x, a multiply-add a
    tap and the gate's product, forward once and backward twice. A hundredth
    of a percent of the step: the bytes are what the operator costs."""
    return (3.0 * (2 * cfg["conv_L_cache"] + 2) * cfg["hidden_size"]
            * cfg["sequence_length"])


def conv_core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """What the operator must move for one layer and sequence: forward reads
    the projection's output (3 D a position) and writes the gated result
    (D); backward reads the projection's output and the result's cotangent
    and writes the projection's: 3 + 1 and 3 + 1 + 3 arrays of S x D. A
    layer that is run again reads and writes the forward's once more, which
    is the program's choice and not counted."""
    return 11.0 * cfg["hidden_size"] * cfg["sequence_length"] * itemsize


def attn_core_flops_per_sample(cfg: dict) -> float:
    """The softmax core of one attention layer over one sequence, the causal
    half: forward 2 matmuls (QK^T, PV), backward 4 (dV, dP, dQ, dK), each 2
    operations a seen pair and feature, over the query heads. What the
    two-pass backward recomputes is not counted."""
    s = cfg["sequence_length"]
    return 6 * 2.0 * (s * s / 2) * cfg["num_attention_heads"] * head_dim(cfg)


def attn_core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv: 6 arrays at the query heads and 6 at the key/value
    heads, of S x head size."""
    return (6.0 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])
            * head_dim(cfg) * cfg["sequence_length"] * itemsize)


def mixer_params_per_token(cfg: dict, mixer: str) -> float:
    """Parameters of one mixer that multiply every token. The convolution:
    W_in (D, 3 D), the taps a channel, W_out. Attention: W_q, W_k, W_v, W_o.
    The q/k norms do no matmul."""
    d = cfg["hidden_size"]
    if mixer == CONV:
        return 3 * d * d + cfg["conv_L_cache"] * d + d * d
    hd = head_dim(cfg)
    return (2 * d * cfg["num_attention_heads"] * hd
            + 2 * d * cfg["num_key_value_heads"] * hd)


def router_params_per_token(cfg: dict) -> float:
    """A layer's router, over all published experts."""
    return cfg["hidden_size"] * cfg["published"]["num_experts"]


def ffn_params_per_token(cfg: dict, ffn: str) -> float:
    """The dense feed-forward's three matrices, or the router over all
    published experts and the expected share of a token's routed-expert
    passes (no shared expert)."""
    d = cfg["hidden_size"]
    if ffn == DENSE:
        return 3.0 * d * cfg["intermediate_size"]
    return (router_params_per_token(cfg)
            + expected_expert_passes(cfg) * 3 * d * cfg["moe_intermediate_size"])


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters that multiply every token: every layer's mixer and
    feed-forward, and the tied matrix once, as the head's product over the
    rows held (the embedding is a lookup). The norms' scales and the
    selection bias do no matmul."""
    return (cfg["vocab_size"] * cfg["hidden_size"]
            + sum(mixer_params_per_token(cfg, mixer) + ffn_params_per_token(cfg, ffn)
                  for mixer, ffn in layer_types(cfg)))


def flops_per_sample(cfg: dict) -> float:
    """Operations the forward and backward passes require for one sequence
    of `sequence_length` tokens: the projections, the taps, the dense
    layers, routers, the held experts' expected share, the tied head and the
    causal half of every attention layer's core; no recomputation. A router
    that is not trained has no weight-gradient product: one of its three
    passes is not required."""
    kinds = layer_types(cfg)
    idle = 0 if cfg["routers_trained"] else (
        sum(ffn == SPARSE for _, ffn in kinds) * router_params_per_token(cfg))
    return (2 * (3.0 * matmul_params_per_token(cfg) - idle) * cfg["sequence_length"]
            + sum(mixer == ATTENTION for mixer, _ in kinds)
            * attn_core_flops_per_sample(cfg))


# -- the per-layer metrics' common part -------------------------------------

CORE_SCOPES = {CONV: "sconv_core", ATTENTION: "attn_full"}
_COUNTS = {CONV: (conv_core_flops_per_sample, conv_core_bytes_per_sample),
           ATTENTION: (attn_core_flops_per_sample, attn_core_bytes_per_sample)}


def core_ms(record, trace, mixer: str):
    """Own time a step of the device ops under `sconv_core` (the gated
    convolution's forward and backward kernels and the taps' sums added up)
    or `attn_full` (the flash forward kernel, the two backward kernels, the
    row sums between them and the layout copies at their doors), of every
    layer of the kind."""
    return scope_own_ms(record, trace, {CORE_SCOPES[mixer]})


def core_roofline_pct(record, trace, mixer: str):
    """The least time the chip could take for the cores of the layers of one
    kind, the larger of their required operations over the bf16 peak and
    their required bytes over the memory peak (`peaks.json`), over the time
    they took, in %: the bytes bound the convolution, the operations the
    attention. None where there is no time to divide by, and of a record of
    another family's configuration."""
    from benchmark.harness import load_peaks

    ms = core_ms(record, trace, mixer)
    if not ms:
        return None if ms is None else 0.0
    cfg = cell_config(record)
    if cfg.get("family") != "lfm2_moe":
        return None
    peaks = load_peaks(record["device"]["kind"])
    n = record["samples_per_step"] * sum(m == mixer for m, _ in layer_types(cfg))
    flops, moved = (count(cfg) for count in _COUNTS[mixer])
    roof_s = max(n * flops / peaks["bf16_flops"],
                 n * moved / peaks["hbm_bytes_per_s"])
    return 100.0 * roof_s / (ms * 1e-3)


def mix_ms(record, trace):
    """Own time a step under `sconv` that is not the operator's: `sconv`
    less `sconv_core`, the norm before the mixer and both projections."""
    whole = scope_own_ms(record, trace, {"sconv"})
    core = core_ms(record, trace, CONV)
    if whole is None or core is None:
        return None
    return whole - core

