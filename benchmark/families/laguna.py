"""The Laguna family: kungfu_tpu.models.transformer under a configuration
file whose keys are the source's (a Hugging Face `config.json` of
`model_type` laguna): layers that differ in kind (full and sliding-window
attention with their own query-head counts and rotary rules, a dense first
feed-forward, expert layers after it), grouped heads, a per-head output
gate, renormalised and scaled gates over 256 routed experts of which this
chip holds a share, a shared expert, an untied head over a slice of the
vocabulary. The system under test is imported; the operation counts, the
batches and the plain reference are the benchmark's own.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.olmoe import (EXPERT_KERNELS, cell_config,
                                      scope_own_ms)

REFERENCE_SAMPLES = 1  # one sequence_length-token sequence

# The program computes in bfloat16 and the reference in float32; router, head
# and loss are float32 in both. Each tolerance is set from two readings on
# the chip at the published widths, 8,192 tokens and the initial parameters
# (PERF.md, PR 33): the largest error of the program over its seeds, and the
# error of the same program with every matrix rounded to float8_e4m3 (3
# mantissa bits, the nearest precision below bfloat16), which has to fail.
# Gradients, as one vector |g - g_ref| / |g_ref|: 3.15 to 3.23 % over 18
# seeds; with 8-bit operands 32.2 and 32.3 %. GRAD_RTOL is 2.5 times the
# largest reading and a quarter of the 8-bit one. (OLMoE's cell reads 4 to 5
# %: there a 4,096-position core with q/k norms carries the error; here the
# dense layer and the projections, 90 % of the parameters, read what
# `bert_base`'s matmuls read three times over.)
# The loss: 1.3e-6 to 5.5e-5 of itself over the same seeds; LOSS_RTOL is
# 3.7 times the largest. The loss cannot see 8-bit operands at every seed
# (3.5e-4 and 1.0e-4: the logits are small at the initial parameters): the
# gradients decide, as for the other families.
# The router's choice is discrete: the program's normed token is a bfloat16
# and the reference's a float32, so a token whose 10th and 11th probabilities
# differ by less than that rounding moves them takes another 10th expert;
# `differing_choices` counts them, and they are in the readings. No window,
# heads read from another group, no head gate, rotary over the whole head, no
# YaRN factor, gates not renormalised or not scaled, no shared expert read
# 28 to 136 % on a state in which they weigh (tests/test_laguna_layers.py).
LOSS_RTOL = 2e-4
GRAD_RTOL = 8e-2

REFERENCE_QUERY_BLOCK = 256  # 72 heads x 256 x 8,192 float32 scores: 0.6 GB


def _layers(cfg: dict) -> list:
    """A dict a layer run here: heads, window (0: none), the source's rope
    group of the layer's kind, the feed-forward's kind, and whether the
    program runs the layer again in its backward pass."""
    out = []
    for l in range(cfg["num_hidden_layers"]):
        kind = cfg["layer_types"][l]
        out.append({
            "heads": cfg["num_attention_heads_per_layer"][l],
            "window": cfg["sliding_window"] if kind == "sliding_attention" else 0,
            "rope": cfg["rope_parameters"][kind],
            "ffn": cfg["mlp_layer_types"][l],
            "remat": kind in cfg["recomputed_layer_types"],
        })
    return out


def model_config(cfg: dict):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    layers = _layers(cfg)
    dense = [i for i, layer in enumerate(layers) if layer["ffn"] == "dense"]
    if (cfg["tie_word_embeddings"] or cfg["attention_bias"]
            or cfg["gating"] != "per-head" or cfg["router_scores"] != "softmax"
            or cfg["moe_router_logit_softcapping"]
            or cfg["moe_apply_router_weight_on_input"]
            or cfg["decoder_sparse_step"] != 1
            or set(cfg["gating_types"]) != {"per_head"}
            or dense != [i for i in cfg["mlp_only_layers"] if i < len(layers)]):
        raise ValueError("the laguna family runs Laguna-S-2.1's layer as "
                         "published: an untied head, no bias, a gate a head, "
                         "softmax scores with no capping, gates on the "
                         "experts' outputs, mlp_only_layers dense")

    def kind(layer):
        rope = layer["rope"]
        yarn = ()
        if rope["rope_type"] == "yarn":
            yarn = (float(rope["factor"]),
                    rope["original_max_position_embeddings"],
                    float(rope["beta_fast"]), float(rope["beta_slow"]),
                    float(rope["attention_factor"]))
        elif rope["rope_type"] != "default":
            raise ValueError(f"rope_type {rope['rope_type']!r}")
        dense = layer["ffn"] == "dense"
        return (("n_heads", layer["heads"]), ("window", layer["window"]),
                ("rope_theta", float(rope["rope_theta"])),
                ("rotary_share", float(rope["partial_rotary_factor"])),
                ("yarn", yarn),
                ("layer_remat", layer["remat"]),
                ("ffn", "swiglu" if dense else "moe"),
                ("d_ff", cfg["intermediate_size"] if dense
                 else cfg["moe_intermediate_size"]))

    return TransformerConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["moe_intermediate_size"],
        max_seq=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        positions="rope", norm_eps=cfg["rms_norm_eps"],
        ffn="moe", n_experts=cfg["published"]["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        tied_head=False,
        attn_core=cfg["attention_core"],
        flash_blocks=tuple(cfg["flash_blocks"]),
        flash_interpret=bool(cfg.get("flash_interpret", False)),
        head_size=cfg["head_dim"], n_kv_heads=cfg["num_key_value_heads"],
        head_gate=True,
        gates="renorm" if cfg["norm_topk_prob"] else "raw",
        routed_scale=float(cfg["moe_routed_scaling_factor"]),
        experts_held=(cfg["first_expert_held"], cfg["num_experts"]),
        shared_ff=cfg["shared_expert_intermediate_size"],
        layer_kinds=tuple(kind(layer) for layer in layers),
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
    return dict(layers=_layers(cfg), head_dim=cfg["head_dim"],
                kv_heads=cfg["num_key_value_heads"], eps=cfg["rms_norm_eps"],
                top_k=cfg["num_experts_per_tok"],
                routed_scale=float(cfg["moe_routed_scaling_factor"]),
                renormalise=bool(cfg["norm_topk_prob"]),
                first_held=cfg["first_expert_held"],
                query_block=REFERENCE_QUERY_BLOCK)


def reference_loss_and_grads(cfg: dict, state, batch):
    from benchmark.reference import laguna as ref

    return ref.loss_and_grads(state, batch, **_hyper(cfg))


def routing_stats(cfg: dict, state, batch) -> dict:
    """The program's routing counters on one host batch, as plain numbers,
    an entry an expert layer: token-choices computed per held expert,
    `held_rows` their sum, `dropped` (0 by construction) and the busiest
    held expert's load over the mean of all 256. Outside the step: the step
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

    from benchmark.reference import laguna as ref
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
    them by one. Ids are uniform over the rows of the vocabulary held here,
    and not skewed as the other families' are: over a share of the experts
    the step's work is the token-choices that land on the experts held, a
    random router is balanced only over diverse inputs, and under the cubic
    skew one id is 1/23 of all tokens and takes the same ten experts every
    time, held or not as the seed has it (PERF.md, PR 33)."""
    rng = np.random.default_rng([seed, i])
    return rng.integers(0, cfg["vocab_size"],
                        (n, cfg["sequence_length"] + 1), dtype=np.int32)


# -- operation counts (2 a multiply-add; backward twice the forward; nothing
#    that is recomputed is counted) ------------------------------------------


def expected_expert_passes(cfg: dict) -> float:
    """Routed-expert passes a token that fall on the experts held here, in
    expectation under a balanced router: top_k x held / published."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["published"]["num_experts"])


def seen_pairs(cfg: dict, window: int) -> float:
    """Query-key pairs a head's mask lets through over one sequence: the
    causal half S^2 / 2, or the band S x window - window^2 / 2."""
    s = cfg["sequence_length"]
    return s * window - window * window / 2 if window else s * s / 2


def core_flops_per_sample(cfg: dict, layer: dict) -> float:
    """The attention core of one layer over one sequence: forward 2 matmuls
    (QK^T, PV), backward 4 (dV, dP, dQ, dK), each 2 operations a seen pair
    and feature, over the layer's query heads. What the two-pass backward
    recomputes (QK^T twice more, dP once more) is not counted, as `mfu_pct`
    does not."""
    return (6 * 2.0 * seen_pairs(cfg, layer["window"])
            * layer["heads"] * cfg["head_dim"])


def core_bytes_per_sample(cfg: dict, layer: dict, itemsize: int = 2) -> float:
    """What the core must move for one layer and sequence: forward reads q,
    k, v and writes o; backward reads q, k, v, o, do and writes dq, dk, dv:
    12 arrays of S x heads x head size, q, o, do, dq at the layer's query
    heads (6) and k, v, dk, dv at the key/value heads (6)."""
    return (6.0 * (layer["heads"] + cfg["num_key_value_heads"])
            * cfg["sequence_length"] * cfg["head_dim"] * itemsize)


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters that multiply every token: a layer's four projections and
    its head gate; the dense feed-forward, or the router, the shared expert
    and the expected share of a token's routed-expert passes; the untied
    head over the rows held. Embedding lookups and the norms' scales do no
    matmul."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    total = cfg["vocab_size"] * d
    for layer in _layers(cfg):
        h = layer["heads"]
        total += 2 * d * h * hd + 2 * d * cfg["num_key_value_heads"] * hd + d * h
        if layer["ffn"] == "dense":
            total += 3 * d * cfg["intermediate_size"]
        else:
            total += (d * cfg["published"]["num_experts"]
                      + 3 * d * cfg["shared_expert_intermediate_size"]
                      + expected_expert_passes(cfg) * expert)
    return total


def flops_per_sample(cfg: dict) -> float:
    """Operations the forward and backward passes require for one sequence
    of `sequence_length` tokens: the projections, gates, routers, shared
    experts, the dense layer, the held experts' expected share, the untied
    head, the causal half of the full cores and the band of the sliding
    ones; no recomputation."""
    return (3.0 * 2 * matmul_params_per_token(cfg) * cfg["sequence_length"]
            + sum(core_flops_per_sample(cfg, layer) for layer in _layers(cfg)))


# -- the per-layer metrics' common part -------------------------------------

CORE_SCOPES = {"window": "attn_window", "full": "attn_full"}


def core_ms(record, trace, which: str):
    """Own time a step of the device ops under `attn_window` or `attn_full`:
    the flash forward kernel, the two backward kernels and the row sums
    between them, of the sliding or of the full layers."""
    return scope_own_ms(record, trace, {CORE_SCOPES[which]})


def core_roofline_pct(record, trace, which: str):
    """The least time the chip could take for the sliding or the full
    layers' cores, the larger of their required operations over the bf16
    peak and their required bytes over the memory peak (`peaks.json`), over
    the time they took, in %. None where there is no time to divide by."""
    from benchmark.harness import load_peaks

    ms = core_ms(record, trace, which)
    if not ms:
        return None if ms is None else 0.0
    cfg = cell_config(record)
    peaks = load_peaks(record["device"]["kind"])
    mine = [l for l in _layers(cfg) if bool(l["window"]) == (which == "window")]
    n = record["samples_per_step"]
    roof_s = sum(max(n * core_flops_per_sample(cfg, l) / peaks["bf16_flops"],
                     n * core_bytes_per_sample(cfg, l) / peaks["hbm_bytes_per_s"])
                 for l in mine)
    return 100.0 * roof_s / (ms * 1e-3)
