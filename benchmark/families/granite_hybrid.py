"""The Granite 4.0-H family (granite-4.0-h-micro): kungfu_tpu.models.
transformer under a configuration file whose keys are the source's (a Hugging
Face `config.json` of `model_type` granitemoehybrid): every layer a mixer and
a gated-silu feed-forward of `shared_intermediate_size`, the mixer by
`layer_types` a Mamba-2 one (64 heads of 64, a state of 128, one group, a
convolution of 4 taps with a bias, a norm over all features behind the gate)
or softmax attention of 32 query heads on 8 key/value heads of 64 with no
position signal; four multipliers (embedding, attention scores, residual,
logits); a tied head over a slice of the vocabulary; no routed expert. The
batches are packed documents: a row is several documents laid end to end,
each ended by the end-of-document id, and the model keeps them apart. The
system under test is imported; the operation and byte counts, the batches
and the plain reference are the benchmark's own.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from benchmark.families.olmoe import cell_config, scope_own_ms

REFERENCE_SAMPLES = 1  # one packed row of sequence_length + 1 ids

# The program computes in bfloat16 and the reference in float32; step, decay,
# the scan's state, the norms' statistics, head and loss are float32 in both.
# Each tolerance is set from two readings on the chip at the published widths,
# 8,192 packed tokens and the initial parameters (my chip runs, PR 52; PERF.md
# section 6): the largest error of the program over its seeds, and the error
# of the same program with every matrix rounded to float8_e4m3 (3 mantissa
# bits, the nearest precision below bfloat16), which has to fail.
# Gradients, as one vector |g - g_ref| / |g_ref|: 0.02683 to 0.02739 over ten
# runs on nine seeds (median 0.02716); 0.2830 and 0.2854 in 8 bits. The limit
# stands between, 1.64 times the largest reading and 0.16 of the 8-bit one.
# Lower than the Nemotron family's 0.042 to 0.054 on the same scan,
# convolution and norm: no expert layer, whose held experts' matrices read
# 0.10 to 0.19 there.
# The loss: 7.1e-7 to 3.7e-6 over those runs, 5.8e-6 and 4.0e-5 in 8 bits: the
# precision hardly moves it, so the limit is the other transformer cells' (54
# times the largest reading), and the 8-bit program is refused by its
# gradients and not by its loss.
# The documents' boundaries, each ignored alone in the program at the cell's
# size (seed 2718281828, a sample of 16 documents): in the scan 0.0840, in
# the convolution 0.1229, in attention 0.0479: all three over the limit,
# attention's, one layer of ten whose scores the scale 1/64 keeps flat at the
# initial parameters, by 6 %. On a state in which they weigh each reads over
# twice the limit, as do each multiplier at 1, an untied head, the norm
# before the gate, the norm over 8 groups and a rotary pass
# (tests/test_granite_hybrid_faults.py).
LOSS_RTOL = 2e-4
GRAD_RTOL = 4.5e-2

REFERENCE_QUERY_BLOCK = 256  # 32 heads x 256 x 8,192 float32 scores: 0.27 GB
REFERENCE_POSITION_BLOCK = 128  # 64 heads' (64, 128) states of a block: 0.27 GB

MAMBA, ATTENTION = "mamba", "attention"  # `layer_types`' own names


def layer_types(cfg: dict) -> list:
    """The kind of each layer run here: the first `num_hidden_layers` of the
    published `layer_types`, which the file keeps whole."""
    kinds = list(cfg["layer_types"][:cfg["num_hidden_layers"]])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError(f"layer_types {cfg['layer_types']!r} do not name "
                         f"{cfg['num_hidden_layers']} layers, mamba or attention")
    return kinds


def end_of_document(cfg: dict) -> int:
    return cfg["documents"]["end_of_document_id"]


def model_config(cfg: dict):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    if (not cfg["tie_word_embeddings"] or cfg["attention_bias"]
            or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"]
            or cfg["hidden_act"] != "silu" or cfg["num_local_experts"]
            or cfg["num_experts_per_tok"]
            or cfg["position_embedding_type"] != "nope"
            or cfg["normalization_function"] != "rmsnorm"
            or cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            != cfg["mamba_expand"] * cfg["hidden_size"]):
        raise ValueError("the granite_hybrid family runs granite-4.0-h-micro's "
                         "layers as published: a tied head, no bias but the "
                         "convolution's, silu, no routed expert, no position "
                         "signal, RMSNorm, a mixer of expand x hidden features")
    recomputed = cfg["recomputed_layer_types"]

    def kind(name):
        return (("mixer", "mamba2" if name == MAMBA else "attention"),
                ("layer_remat", name in recomputed))

    return TransformerConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["shared_intermediate_size"],
        max_seq=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        positions="none",
        norm_eps=cfg["rms_norm_eps"],
        ffn="swiglu",
        tied_head=True,
        attn_core=cfg["attention_core"],
        flash_blocks=tuple(cfg["flash_blocks"]),
        flash_interpret=bool(cfg.get("flash_interpret", False)),
        head_size=cfg["hidden_size"] // cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        mixer="mamba2",
        ssm_dims=(cfg["mamba_n_heads"], cfg["mamba_d_head"],
                  cfg["mamba_d_state"], cfg["mamba_n_groups"]),
        conv_taps=cfg["mamba_d_conv"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        end_of_document=end_of_document(cfg),
        layer_kinds=tuple(kind(t) for t in layer_types(cfg)),
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
    return dict(layers=layer_types(cfg),
                ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
                ssm_state=cfg["mamba_d_state"], ssm_groups=cfg["mamba_n_groups"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
                eps=cfg["rms_norm_eps"],
                embedding_multiplier=float(cfg["embedding_multiplier"]),
                attention_multiplier=float(cfg["attention_multiplier"]),
                residual_multiplier=float(cfg["residual_multiplier"]),
                logits_scaling=float(cfg["logits_scaling"]),
                end_of_document=end_of_document(cfg),
                query_block=REFERENCE_QUERY_BLOCK,
                position_block=REFERENCE_POSITION_BLOCK)


def reference_loss_and_grads(cfg: dict, state, batch):
    from benchmark.reference import granite_hybrid as ref

    return ref.loss_and_grads(state, batch, **_hyper(cfg))


def packing_stats(cfg: dict, batch) -> dict:
    """The program's own account of one host batch (`models.transformer.
    packing_stats`), as plain numbers a row: documents, the shortest and the
    longest, the share of causal pairs that lie within a document. Outside
    the step: the step returns a loss and nothing else."""
    import jax

    from kungfu_tpu.models import transformer

    mc = model_config(cfg)
    stats = jax.jit(lambda t: transformer.packing_stats(t, mc))(batch[:, :-1])
    return {k: np.asarray(v).tolist() for k, v in stats.items()}


# -- the batches: packed documents -------------------------------------------


def _document_lengths(cfg: dict, rng, count: int):
    """`count` lengths drawn independently from the configuration's
    log-normal, rounded to whole tokens and clipped."""
    d = cfg["documents"]
    drawn = rng.lognormal(math.log(d["median"]), d["sigma"], size=count)
    return np.clip(np.rint(drawn), d["shortest"], d["longest"]).astype(np.int64)


def host_batch(cfg: dict, seed: int, i: int, n: int):
    """The i-th host batch of n rows: token ids (n, S + 1), every row packed,
    no position padding, a document's last id the end-of-document id and
    every other id uniform from the seed over the rows of the vocabulary
    held here that are not that one. The loss shifts the ids by one.

    Where the configuration's `documents` holds `rows` (PR 60), a batch of
    one row with i < len(rows), so every batch of the cell's pool, is row i
    of the file: its pieces' lengths in order, the same under every seed, and
    the seed draws the ids alone (`_written_row`). The kernels skip by the
    documents' boundaries, so a step's time follows its row: a pool drawn
    from the seed made the cell's step times a lottery over seeds.

    Any other batch (`harness.SAMPLE_INDEX`, a batch of several rows, a
    configuration without `rows`) draws its documents too (`_drawn_rows`)."""
    rows = cfg["documents"].get("rows", ())
    if n == 1 and i < len(rows):
        return _written_row(cfg, seed, i, rows[i])
    return _drawn_rows(cfg, seed, i, n)


def _written_row(cfg: dict, seed: int, i: int, pieces):
    """One row whose boundaries are `pieces`, the lengths in order of the
    documents' pieces it holds (its head and its tail are pieces of
    documents the row cuts, as `row_documents` counts them): the
    end-of-document id at each piece's last position but the row's last,
    every other id uniform from `default_rng([seed, i])`."""
    width = cfg["sequence_length"] + 1
    if sum(pieces) != width or min(pieces) < 1:
        raise ValueError(f"documents.rows[{i}] holds {sum(pieces)} ids in "
                         f"pieces from {min(pieces)}: a row is {width} ids")
    rng = np.random.default_rng([seed, i])
    ids = rng.integers(1, cfg["vocab_size"], size=width, dtype=np.int32)
    ids[np.cumsum(pieces)[:-1] - 1] = end_of_document(cfg)
    return ids[None]


def _drawn_rows(cfg: dict, seed: int, i: int, n: int):
    """n rows of one stream drawn from the seed. Documents are drawn one
    after another (`_document_lengths`) and laid end to end in the order
    drawn, and the stream is cut into rows of S + 1 ids, so a row begins
    inside a document (its head is a document of its own to the model:
    nothing of it came before) and ends inside one. The cut begins between
    one and two of the longest documents' lengths into the stream, as a row
    of a long stream's middle does, and not at a document's first id."""
    rng = np.random.default_rng([seed, i])
    d = cfg["documents"]
    need = n * (cfg["sequence_length"] + 1)
    start = d["longest"] + int(rng.integers(0, d["longest"]))
    lengths = _document_lengths(cfg, rng, (start + need) // d["shortest"] + 1)
    ends = np.cumsum(lengths) - 1
    ids = rng.integers(1, cfg["vocab_size"], size=start + need, dtype=np.int32)
    ids[ends[ends < ids.size]] = end_of_document(cfg)
    return ids[start:].reshape(n, cfg["sequence_length"] + 1)


def rows_by_rule(cfg: dict) -> list:
    """The rows `documents.rows` holds, made again by `documents.rows_rule`:
    the first `seeds` x `batches` rows the free generator draws
    (`_drawn_rows(cfg, seed, i, 1)`, seed-major), ranked by
    `within_document_pairs` (ties in the order drawn), and of each of `keep`
    equal parts of the ranking the middle row, as the lengths of its pieces
    over all S + 1 ids: eight rows at the octile midpoints of the
    attention layer's work, the fourth and fifth beside the distribution's
    median, the last at its 94th percentile."""
    rule = cfg["documents"]["rows_rule"]
    drawn = [_drawn_rows(cfg, seed, i, 1)
             for seed in range(rule["seeds"]) for i in range(rule["batches"])]
    ranked = np.argsort([within_document_pairs(cfg, b) for b in drawn],
                        kind="stable")
    part = len(drawn) // rule["keep"]
    return [row_documents(cfg, drawn[ranked[k * part + part // 2]][0])
            for k in range(rule["keep"])]


def row_documents(cfg: dict, tokens) -> list:
    """The lengths of the documents of one row of token ids (S,), in order,
    by the benchmark's own count (numpy; the program's is
    `models.transformer.packing_stats`, and the tests hold the two
    together): a document ends with the end-of-document id or with the row."""
    tokens = np.asarray(tokens)
    ends = np.flatnonzero(tokens == end_of_document(cfg)) + 1
    edges = np.unique(np.concatenate([[0], ends, [tokens.size]]))
    return np.diff(edges).tolist()


def within_document_pairs(cfg: dict, batch) -> float:
    """Causal (query, key) pairs of a host batch's rows that lie within one
    document, the diagonal among them, a row: l (l + 1) / 2 a document of l
    positions, over the S positions a row feeds the model."""
    return float(np.mean([sum(l * (l + 1) // 2
                              for l in row_documents(cfg, row[:-1]))
                          for row in np.asarray(batch)]))


def causal_pairs(cfg: dict) -> float:
    """Every causal pair of a row of one document: S (S + 1) / 2."""
    s = cfg["sequence_length"]
    return s * (s + 1) / 2


@functools.cache
def _expected_pairs(median: float, sigma: float, shortest: int, longest: int,
                    s: int) -> float:
    """The expectation of `within_document_pairs` for a row of s positions
    cut from the middle of a long stream. With p_l the probability of a
    length l (the log-normal's mass on [l - 1/2, l + 1/2), the tails on the
    two clips), mu its mean and F(a) = P(L > a): a position of the stream
    is a document's (a + 1)-th with probability F(a) / mu (the renewal
    theorem), so it is at least its (a + 1)-th with T(a) = sum_{a' >= a}
    F(a') / mu, T(0) = 1. Position i of the row (from 0) sees min(its place
    in its document, i + 1) keys, in expectation sum_{a <= i} T(a); the
    row's pairs are the sum over i."""
    def cdf(x):  # of the unclipped log-normal
        return 0.5 * (1 + math.erf((math.log(x) - math.log(median))
                                   / (sigma * math.sqrt(2))))

    lengths = np.arange(shortest, longest + 1)
    upper = np.array([cdf(l + 0.5) for l in lengths])
    p = np.diff(np.concatenate([[0.0], upper]))
    p[-1] += 1.0 - upper[-1]
    mu = float(np.sum(p * lengths))
    longer = np.ones(longest)  # F(a) for a in 0..longest-1
    longer[shortest:] = 1.0 - np.cumsum(p)[:-1]
    at_least = np.cumsum(longer[::-1])[::-1] / mu  # T(a)
    at_least = np.concatenate([at_least, np.zeros(max(0, s - longest))])[:s]
    return float(np.sum(np.cumsum(at_least)))


def expected_within_document_pairs(cfg: dict) -> float:
    """`_expected_pairs` of the configuration's documents and row: what
    `flops_per_sample`, which sees no seed, counts the attention layer by."""
    d = cfg["documents"]
    return _expected_pairs(d["median"], d["sigma"], d["shortest"], d["longest"],
                           cfg["sequence_length"])


# -- operation and byte counts (2 a multiply-add; backward twice the forward;
#    nothing that is recomputed is counted) ----------------------------------


def ssm_inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def ssm_core_flops_per_sample(cfg: dict) -> float:
    """The state-space scan of one layer over one row, as the chunked form
    at the configuration's `mamba_chunk_size` C states it, whatever
    implements it and whatever chunk that takes: a position of a group the
    scores C B^T against its chunk (2 C N), a position of a head their
    product with x (2 C P), the chunk's state B^T x and its read-out C S (2
    N P each); forward once, backward twice. Document boundaries take no
    operation away: a chunk is computed whole and masked."""
    C, N, P = cfg["mamba_chunk_size"], cfg["mamba_d_state"], cfg["mamba_d_head"]
    a_position = (cfg["mamba_n_groups"] * 2.0 * C * N
                  + cfg["mamba_n_heads"] * (2.0 * C * P + 4.0 * N * P))
    return 3 * a_position * cfg["sequence_length"]


def ssm_core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """What the scan must move for one layer and row: forward reads x, B, C,
    Delta and writes y; backward reads x, B, C, Delta, dy and writes dx, dB,
    dC, dDelta: 5 arrays at the heads' width, 6 at a group's state size a
    group, 3 of a float32 a head and position, and the documents' numbers,
    a whole number of 4 bytes a position, read once each way. The
    chunk-boundary states the program keeps between its passes are its own
    choice and not counted."""
    return cfg["sequence_length"] * (
        5.0 * ssm_inner(cfg) * itemsize
        + 6.0 * cfg["mamba_n_groups"] * cfg["mamba_d_state"] * itemsize
        + 3.0 * cfg["mamba_n_heads"] * 4
        + 2.0 * 4)


def attn_core_flops(cfg: dict, pairs: float) -> float:
    """The softmax core of one attention layer over `pairs` seen (query,
    key) pairs: forward 2 matmuls (QK^T, PV), backward 4 (dV, dP, dQ, dK),
    each 2 operations a pair and feature, over the query heads. What the
    two-pass backward recomputes, and what a masked block computes to
    nothing, is not counted."""
    return (6 * 2.0 * pairs * cfg["num_attention_heads"]
            * (cfg["hidden_size"] // cfg["num_attention_heads"]))


def attn_core_bytes_per_sample(cfg: dict, itemsize: int = 2) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv: 6 arrays at the query heads and 6 at the key/value
    heads, of S x head size; and the documents' numbers once each way."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["sequence_length"] * (
        6.0 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])
        * hd * itemsize + 2.0 * 4)


def ffn_params_per_token(cfg: dict) -> float:
    """A layer's gated feed-forward: gate, up and down."""
    return 3.0 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def layer_params_per_token(cfg: dict, kind: str) -> float:
    """Parameters of one layer that multiply every token: its feed-forward,
    and its mixer's. Mamba-2: W_in, the convolution's taps over the x, B, C
    channels, W_out. Attention: W_q, W_k, W_v, W_o."""
    d = cfg["hidden_size"]
    if kind == MAMBA:
        inner = ssm_inner(cfg)
        conv = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
        mixer = (d * (inner + conv + cfg["mamba_n_heads"])
                 + cfg["mamba_d_conv"] * conv + inner * d)
    else:
        hd = d // cfg["num_attention_heads"]
        mixer = (2 * d * cfg["num_attention_heads"] * hd
                 + 2 * d * cfg["num_key_value_heads"] * hd)
    return mixer + ffn_params_per_token(cfg)


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters that multiply every token: every layer's, and the tied
    matrix once, as the head's product over the rows held (the embedding is
    a lookup). The norms' scales, the convolution's bias and the numbers a
    head (dt_bias, A_log, D) do no matmul."""
    return (cfg["vocab_size"] * cfg["hidden_size"]
            + sum(layer_params_per_token(cfg, t) for t in layer_types(cfg)))


def flops_per_sample(cfg: dict) -> float:
    """Operations the forward and backward passes require for one packed row
    of `sequence_length` tokens: projections, convolutions, feed-forwards,
    the tied head, the scan of the Mamba-2 layers, and of the attention
    layers the causal pairs that lie within a document, in expectation under
    the configuration's documents (`expected_within_document_pairs`: this
    function sees no seed; attention is under 2 % of the count either way);
    no recomputation."""
    kinds = layer_types(cfg)
    return (2 * 3.0 * matmul_params_per_token(cfg) * cfg["sequence_length"]
            + kinds.count(MAMBA) * ssm_core_flops_per_sample(cfg)
            + kinds.count(ATTENTION) * attn_core_flops(
                cfg, expected_within_document_pairs(cfg)))


# -- the per-layer metrics' common part -------------------------------------

CORE_SCOPES = {MAMBA: "ssm_core", ATTENTION: "attn_full"}


def pool(record) -> list:
    """The host batches the run's steps cycled, made again from its seed."""
    from benchmark import manifest

    cell = manifest.cell(manifest.load(), record["workload"])
    return [host_batch(cell["config"], record["seed"], i,
                       record["samples_per_step"])
            for i in range(cell["traffic"]["pool"])]


def pool_within_document_pairs(record):
    """`within_document_pairs` a row, the mean over the run's pool (since
    PR 60 the configuration's written rows, one mean under every seed): the
    traced steps' time is a median over steps that cycle the pool. Of a
    record that does not say which seed its batches came from, the
    expectation under the configuration's documents."""
    cfg = cell_config(record)
    if "seed" not in record:
        return expected_within_document_pairs(cfg)
    return float(np.mean([within_document_pairs(cfg, batch)
                          for batch in pool(record)]))


def core_ms(record, trace, kind: str):
    """Own time a step of the device ops under `ssm_core` (the scan's
    kernels, forward and backward, what makes their marks of the documents'
    numbers and what adds up a group's dq and dk) or `attn_full` (the flash
    forward kernel, the two backward kernels, the row sums between them, the
    documents' numbers laid out for them and the layout copies at their
    doors)."""
    return scope_own_ms(record, trace, {CORE_SCOPES[kind]})


def core_roofline_pct(record, trace, kind: str):
    """The least time the chip could take for the cores of the layers of one
    kind, the larger of their required operations over the bf16 peak and
    their required bytes over the memory peak (`peaks.json`), over the time
    they took, in %. The attention's required operations are the
    within-document causal pairs of the run's own batches
    (`pool_within_document_pairs`, from `record["seed"]`; their expectation
    where a record names no seed). None where there is no time to divide by."""
    from benchmark.harness import load_peaks

    ms = core_ms(record, trace, kind)
    if not ms:
        return None if ms is None else 0.0
    cfg = cell_config(record)
    if "documents" not in cfg:  # a record of another configuration's run
        return None
    peaks = load_peaks(record["device"]["kind"])
    n = record["samples_per_step"] * layer_types(cfg).count(kind)
    if kind == MAMBA:
        flops, moved = ssm_core_flops_per_sample(cfg), ssm_core_bytes_per_sample(cfg)
    else:
        flops = attn_core_flops(cfg, pool_within_document_pairs(record))
        moved = attn_core_bytes_per_sample(cfg)
    roof_s = max(n * flops / peaks["bf16_flops"],
                 n * moved / peaks["hbm_bytes_per_s"])
    return 100.0 * roof_s / (ms * 1e-3)


def mix_ms(record, trace):
    """Own time a step under `ssm` that is not the scan's: `ssm` less
    `ssm_core`."""
    whole = scope_own_ms(record, trace, {"ssm"})
    core = core_ms(record, trace, MAMBA)
    if whole is None or core is None:
        return None
    return whole - core


def dead_block_share(cfg: dict, batches) -> float:
    """Of the (query block, key block) pairs the flash kernels visit under
    the causal mask alone, the share that a document boundary leaves dead:
    every key of the block of an earlier document than every query of it.
    The kernels of PR 52 visited and masked them; since PR 53 they run no
    body and fetch nothing there, so a row's core time follows this share."""
    blk_q, blk_k = cfg["flash_blocks"]
    visited = dead = 0
    for batch in batches:
        for row in np.asarray(batch):
            tokens = row[:-1]
            behind = np.concatenate([[False], tokens[:-1] == end_of_document(cfg)])
            doc = np.cumsum(behind)
            for q_off in range(0, tokens.size, blk_q):
                for k_off in range(0, q_off + blk_q, blk_k):
                    visited += 1
                    dead += doc[k_off + blk_k - 1] < doc[q_off]
    return dead / visited
