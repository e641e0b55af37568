"""The Xing4.0 family (Xing4.0-29B-A4B): kungfu_tpu.models.transformer under a
configuration file whose keys are the source's (a Hugging Face `config.json`
of `model_type` xing4_0): four residual streams a position, mixed around
every branch of a layer by maps the layer computes from them
(manifold-constrained hyper-connections: `hc_mult` 4, a Sinkhorn-Knopp
projection of `hc_sinkhorn_iters` 20 passes, `hc_eps`, a clamp on the logits),
over DeepSeek-V3's layer: latent attention (a q latent of 768 and a key/value
latent of 512 with their norms, 32 heads of 128 unrotated and 64 rotated q/k
features on 128 value features, one rotated key for all heads) under YaRN
(factor 64 over 4,096 positions, the softmax's scale times mscale^2), leading
dense feed-forwards and expert layers after them, sigmoid router scores with
a selection bias, the chosen scores renormalised and scaled, 64 routed experts
of which this chip holds a share beside a shared expert, a
multi-token-prediction module where the file has it (the cell's leaves it
with a later stage), an untied head over a slice of the vocabulary. What is
GLM-4.7-Flash's too is imported from `families.glm4_moe_lite`. The system
under test is imported; the operation and byte counts, the batches and the
plain reference are the benchmark's own.

`flops_per_sample` counts required work only, the residual path's among it:
the maps' products with Phi (2 n C (2 n + n^2) a position and branch, three
passes) and the two mixings (n C multiply-adds to read, (n^2 + n) C to write,
three passes); the Sinkhorn passes, 20 x 2 x n^2 divisions a position and
branch, are 0.01 % of a branch and are left out.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.families import glm4_moe_lite
from benchmark.families.glm4_moe_lite import (  # noqa: F401  the family's own too
    CORE_SCOPE, DENSE, SPARSE, core_bytes_per_sample, core_flops_per_sample,
    core_ms, expected_expert_passes, head_width, mixer_ms,
    mixer_params_per_token, router_params_per_token, trainable)
from benchmark.families.olmoe import cell_config, scope_own_ms

REFERENCE_SAMPLES = 1  # one sequence_length-token sequence

# The program computes in bfloat16 and the reference in float32; the maps
# (the root mean square over the streams, the product with Phi, the sigmoids,
# the Sinkhorn passes), router, head and loss are float32 in both. Each
# tolerance is set from two readings on the chip at the published widths,
# 4,096 tokens and the initial parameters (PERF.md section 6, PR 71): the
# largest error of the program over its seeds, and the error of the same
# program with every matrix rounded to float8_e4m3 (3 mantissa bits, the
# nearest precision below bfloat16), which has to fail.
# Gradients, as one vector |g - g_ref| / |g_ref|: 3.42 to 6.79 % over 17
# seeds (mean 5.1, deviation 0.8: wider than the GLM-4.7-Flash family's 4 to
# 5 %, whose layer this is on one stream); with 8-bit matrices 24.5 %.
# GRAD_RTOL is 1.25 times the largest reading, 4.4 deviations over the mean,
# and 0.35 of the 8-bit one.
# The loss: 1.1e-6 to 1.43e-4 of itself over the same seeds (the second
# largest 7.3e-5). LOSS_RTOL, the harness's accepted cells' limit, is 6.1
# times the first reading (3.26e-5) and 1.4 times the largest (PERF.md
# section 7 asks what a later issue should do about so little room). The
# loss does not see 8-bit matrices (1.3e-4: the logits are small at the
# initial parameters): the gradients decide, as for the other families.
# The maps in bfloat16 fail neither limit at the real size (4.73 % beside
# the same seed's 4.53 %): what holds them to float32 is read off the
# program (tests/benchmark/test_bench_xing4_0.py) and, at the small size,
# tests/test_xing4_0_faults.py. One Sinkhorn pass for 20, a softmax over the
# rows, H_post without its 2, a softmax for H_pre, no dynamic part, exit by
# the first stream, plain rotary frequencies and a scale without mscale^2
# read 18 to 62 % on a state in which they weigh (the same file).
LOSS_RTOL = 2e-4
GRAD_RTOL = 8.5e-2

REFERENCE_QUERY_BLOCK = 256  # 32 heads x 256 x 4,096 float32 scores: 0.13 GB


def _as_run(cfg: dict) -> dict:
    """The file as the GLM-4.7-Flash family's functions read it: the leading
    dense layers that are run here (`dense_layers_run`: they count once, so
    one of the published `first_k_dense_replace` 2) in that key's place."""
    return {**cfg, "first_k_dense_replace": cfg["dense_layers_run"]}


def layer_types(cfg: dict) -> list:
    """The feed-forward of each layer run here: the first `dense_layers_run`
    dense, the others expert layers."""
    return glm4_moe_lite.layer_types(_as_run(cfg))


def blocks(cfg: dict) -> list:
    """The feed-forward of every block a step runs: the layers, and the
    multi-token-prediction module's where the file has it, an expert layer."""
    return glm4_moe_lite.blocks(_as_run(cfg))


def yarn_of(cfg: dict):
    """(factor, original positions, beta_fast, beta_slow, mscale,
    mscale_all_dim) of the file's `rope_scaling`."""
    scaling = cfg["rope_scaling"]
    return (float(scaling["factor"]), scaling["original_max_position_embeddings"],
            scaling["beta_fast"], scaling["beta_slow"], scaling["mscale"],
            scaling["mscale_all_dim"])


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V2's and V3's `yarn_get_mscale`."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def model_config(cfg: dict):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    if (cfg["tie_word_embeddings"] or cfg["attention_bias"]
            or cfg["hidden_act"] != "silu" or not cfg["norm_topk_prob"]
            or (cfg["rope_scaling"] or {}).get("type") != "yarn"
            or cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc"
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1
            or cfg["n_shared_experts"] != 1 or cfg["moe_layer_freq"] != 1
            or cfg["num_nextn_predict_layers"] not in (0, 1) or cfg["hc_mult"] < 2
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]
            or not 1 <= cfg["dense_layers_run"] <= cfg["first_k_dense_replace"]
            or cfg["dense_layers_run"] >= cfg["num_hidden_layers"]):
        raise ValueError("the xing4_0 family runs Xing4.0's layer as "
                         "published: an untied head, no bias, silu, YaRN, "
                         "renormalised sigmoid scores with a selection bias "
                         "and no expert groups, one shared expert, an expert "
                         "layer in every layer behind the dense ones, one "
                         "multi-token-prediction module or none, two residual "
                         "streams or more, a key/value head a query head, of "
                         "the leading dense layers one or more and expert "
                         "layers behind them")
    recomputed = cfg["recomputed_layer_types"]
    factor, original, fast, slow, mscale, mscale_all = yarn_of(cfg)
    hd = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]

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
        yarn=(factor, original, fast, slow,
              yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)),
        attention_multiplier=yarn_mscale(factor, mscale_all) ** 2 / math.sqrt(hd),
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
        streams=cfg["hc_mult"], hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=cfg["hc_eps"],
        hc_clamp=(float(cfg["mhc_h_res_clamp_min"]),
                  float(cfg["mhc_h_res_clamp_max"])),
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
    every expert layer's router matrix, the multi-token-prediction module's
    among them where the tree has one."""
    def routed(layer):
        return {**layer, "router": of(layer["router"])} if "router" in layer else layer

    tree = {**tree, "layers": tuple(routed(stack) for stack in tree["layers"])}
    if "mtp" in tree:
        tree["mtp"] = {**tree["mtp"], "layer": routed(tree["mtp"]["layer"])}
    return tree


def loss_fn(cfg: dict):
    """The model's loss, with the module main + `mtp_loss_weight` x the
    module's. Where the configuration says `routers_trained` false the
    routers' matrices are constants of it, for the Qwen3-Next family's reason
    (`families.qwen3_next.loss_fn`; PERF.md, PR 36). The selection bias is a
    constant of the loss by what it is."""
    import jax

    from kungfu_tpu.models.transformer import transformer_loss

    mc = model_config(cfg)
    if cfg["routers_trained"]:
        return lambda params, batch: transformer_loss(params, batch, mc)
    return lambda params, batch: transformer_loss(
        _with_routers(params, jax.lax.stop_gradient), batch, mc)


def program_loss_and_grads(cfg: dict):
    """The jitted (state, batch) -> (loss, gradients of `trainable(state)`),
    as one device computes them (no mesh): what the reference is compared
    with."""
    import jax

    return jax.jit(jax.value_and_grad(loss_fn(cfg)))


def _hyper(cfg: dict) -> dict:
    return dict(heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
                rope=cfg["qk_rope_head_dim"], value=cfg["v_head_dim"],
                kv_rank=cfg["kv_lora_rank"], rope_theta=float(cfg["rope_theta"]),
                yarn=yarn_of(cfg), eps=cfg["rms_norm_eps"],
                top_k=cfg["num_experts_per_tok"],
                routed_scale=float(cfg["routed_scaling_factor"]),
                first_held=cfg["first_expert_held"],
                mtp_weight=float(cfg["mtp_loss_weight"]),
                streams=cfg["hc_mult"], sinkhorn_iters=cfg["hc_sinkhorn_iters"],
                hc_eps=cfg["hc_eps"],
                clamp=(float(cfg["mhc_h_res_clamp_min"]),
                       float(cfg["mhc_h_res_clamp_max"])),
                query_block=cfg.get("reference_query_block", REFERENCE_QUERY_BLOCK))


def reference_loss_and_grads(cfg: dict, state, batch):
    """The reference's loss and gradients, the routers' set to zero where
    the configuration does not train them (`loss_fn`)."""
    import jax.numpy as jnp

    from benchmark.reference import xing4_0 as ref

    loss, grads = ref.loss_and_grads(state, batch, **_hyper(cfg))
    if not cfg["routers_trained"]:
        grads = _with_routers(grads, jnp.zeros_like)
    return loss, grads


def routing_stats(cfg: dict, state, batch) -> dict:
    """The program's routing counters on one host batch, as plain numbers,
    an entry an expert layer, the module's last where there is one (the
    stats read the ids but the last: the S positions and, with the module,
    the ids one further on). Outside the step: the step returns a loss and
    nothing else."""
    import jax

    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    stats = jax.jit(lambda p, t: transformer.routing_stats(p, t, mc))(
        state, batch[:, :-1])
    return {k: np.asarray(v).tolist() for k, v in stats.items() if k != "chosen"}


def residual_stats(cfg: dict, state, batch) -> dict:
    """The program's counters of the residual maps on one host batch, as
    plain numbers, an entry a layer and branch
    (`transformer.residual_stats`)."""
    import jax

    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    stats = jax.jit(lambda p, t: transformer.residual_stats(p, t, mc))(
        state, batch[:, :-1])
    return {k: np.asarray(v).tolist() for k, v in stats.items()}


def differing_choices(cfg: dict, state, batch) -> int:
    """Token-choices of the program's router that the reference's router
    does not make for the same token, over all expert layers."""
    import jax

    from benchmark.reference import xing4_0 as ref
    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    mine = np.asarray(jax.jit(
        lambda p, t: transformer.routing_stats(p, t, mc)["chosen"])(
            state, batch[:, :-1]))
    theirs = np.asarray(ref.chosen_experts(state, batch, **_hyper(cfg)))
    same = (mine[..., :, None] == theirs[..., None, :]).any(-1)
    return int(mine.size - same.sum())


def host_batch(cfg: dict, seed: int, i: int, n: int):
    """The i-th host batch of n samples: token ids (n, S + 1), and (n, S +
    2) where the file has the module, each row one document (no packing, no
    boundary mask). Ids are uniform over the rows of the vocabulary held
    here, for the GLM-4.7-Flash family's reason (PERF.md, PR 33)."""
    rng = np.random.default_rng([seed, i])
    return rng.integers(
        0, cfg["vocab_size"],
        (n, cfg["sequence_length"] + 1 + cfg["num_nextn_predict_layers"]),
        dtype=np.int32)


# -- operation and byte counts (2 a multiply-add; backward twice the forward;
#    nothing that is recomputed is counted) ----------------------------------


def branches(cfg: dict) -> int:
    """The branches a step mixes the streams around: two a block, the
    module's among them."""
    return 2 * len(blocks(cfg))


def hc_flops_per_token(cfg: dict) -> float:
    """The residual path's required operations a position and branch, one
    pass: the product with Phi, 2 n C (2 n + n^2), and the two mixings, 2 n C
    to read and 2 (n^2 + n) C to write."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return 2.0 * n * c * (2 * n + n * n) + 2.0 * n * c + 2.0 * (n * n + n) * c


def stream_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """The bytes the two mixings of one branch must move for one sequence,
    both ways, whatever implements them, in rows of C features of the
    model's type a position: forward, to read, the n streams in and u out (n
    + 1), to write, the streams and y in and the streams out (2 n + 1);
    backward, of the write, the streams' cotangent, the streams and y in (for
    the maps' own gradients) and the streams' and y's cotangents out (3 n +
    2), of the read, u's cotangent and the streams in and the streams'
    cotangent in and out, added to (3 n + 1): 9 n + 5 rows, 41 of four
    streams. The maps themselves (2 n + n^2 float32 a position) and a layer
    run again are not counted."""
    n = cfg["hc_mult"]
    return (9.0 * n + 5) * cfg["hidden_size"] * itemsize * cfg["sequence_length"]


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters that multiply every token: the layers', the module's and
    the head's as the GLM-4.7-Flash family counts them, and each branch's
    Phi (n C x (2 n + n^2))."""
    n = cfg["hc_mult"]
    return (glm4_moe_lite.matmul_params_per_token(_as_run(cfg))
            + branches(cfg) * n * cfg["hidden_size"] * (2 * n + n * n))


def flops_per_sample(cfg: dict) -> float:
    """Operations the forward and backward passes require for one sequence
    of `sequence_length` tokens: the GLM-4.7-Flash family's sum over this
    file (projections, routers less the weight-gradient product of one that
    is not trained, shared experts, the dense layers, the held experts'
    expected share, the module's projection, the head passes and the causal
    half of every block's core) and the residual path's three passes a
    branch; no recomputation."""
    return (glm4_moe_lite.flops_per_sample(_as_run(cfg))
            + 3.0 * branches(cfg) * hc_flops_per_token(cfg) * cfg["sequence_length"])


# -- the per-layer metrics' common part -------------------------------------

HC_SCOPES = {"hc", "hc_in", "hc_out"}
STREAM_SCOPES = {"hc_read", "hc_write"}


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


def hc_ms(record, trace, scopes=HC_SCOPES):
    """Own time a step of the device ops under any of `scopes`, forward and
    backward: by default all of the residual path (`hc` around the branches,
    `hc_in` and `hc_out` at the streams' entry and exit)."""
    return scope_own_ms(record, trace, scopes)


def stream_roofline_pct(record, trace):
    """The least time the chip could take for the two mixings of every
    branch, their required bytes (`stream_bytes_per_sample`) over the memory
    peak (`peaks.json`), over the own time under `hc_read` and `hc_write`,
    in %. None where there is no time to divide by."""
    from benchmark.harness import load_peaks

    ms = hc_ms(record, trace, STREAM_SCOPES)
    if not ms:
        return None if ms is None else 0.0
    cfg = cell_config(record)
    peaks = load_peaks(record["device"]["kind"])
    moved = (record["samples_per_step"] * branches(cfg)
             * stream_bytes_per_sample(cfg))
    return 100.0 * moved / peaks["hbm_bytes_per_s"] / (ms * 1e-3)
