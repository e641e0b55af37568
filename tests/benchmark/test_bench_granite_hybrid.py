"""The Granite 4.0-H family, its configuration and its eight readers (PR 52):
the whole of `harness.measure` at tiny size on the CPU mesh with packed
batches, the parameter, operation and byte counts against the initialised tree
and sums made by hand, the documents the batches are made of and their
expectation, the readers against a drawn trace, and the configuration file
against the catalog's numbers. Since PR 60 the cell's pool is the eight rows
the configuration's file writes, the same under every seed: the rows, the rule
that chose them, and what still follows the seed.

These tests find the cell and its entries by name, wherever later cells put
them: no position in the manifest is pinned."""

import copy
import json
import time

import jax
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.families import granite_hybrid
from benchmark.launchers.none import OneProcess
from benchmark.layer_metrics import (pk_attn_core_ms, pk_attn_core_roofline_pct,
                                     pk_ffn_ms, pk_segments_ms, pk_ssm_core_ms,
                                     pk_ssm_core_roofline_pct, pk_ssm_mix_ms,
                                     pk_within_doc_pairs_pct)
from drawn_setup import child_marks, drawn_setup

CELL = "granite_4_0_h_micro.ssgd_packed_1chip"
NAME = "granite_4_0_h_micro"
MINE = (("pk_ssm_core_ms", "ms", "lower", "device_trace", "Kernels"),
        ("pk_ssm_core_roofline_pct", "%", "higher", "device_trace", "Kernels"),
        ("pk_ssm_mix_ms", "ms", "lower", "device_trace", "Model"),
        ("pk_attn_core_ms", "ms", "lower", "device_trace", "Kernels"),
        ("pk_attn_core_roofline_pct", "%", "higher", "device_trace", "Kernels"),
        ("pk_ffn_ms", "ms", "lower", "device_trace", "Model"),
        ("pk_segments_ms", "ms", "lower", "device_trace", "Model"),
        ("pk_within_doc_pairs_pct", "%", "lower", "program_counter", "Model"))
JOINED = ("optimizer_ms", "head_loss_ms")

# every mechanism on, at the tests' size (tests/family_cases.py); the kernels
# in interpret mode by a key of the configuration
TINY = dict(hidden_size=64, intermediate_size=96, shared_intermediate_size=96,
            num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
            num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
            mamba_d_head=16, mamba_d_state=16, vocab_size=320,
            sequence_length=128,
            documents=dict(distribution="lognormal", median=24, sigma=1.0,
                           shortest=4, longest=128, end_of_document_id=0),
            flash_blocks=[32, 32], flash_interpret=True)  # 320: no layer's width

# ibm-granite/granite-4.0-h-micro's config.json as the catalog has it
LAYER_TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 9 + ["attention"]
               + ["mamba"] * 9 + ["attention"] + ["mamba"] * 9 + ["attention"]
               + ["mamba"] * 4)
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": LAYER_TYPES, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}


def _real():
    return mf.cell(mf.load(), CELL)["config"]


def _free():
    """The cell's configuration without its written rows: every batch drawn
    from the seed, as all were until PR 60 and as the reference's sample is."""
    config = copy.deepcopy(_real())
    del config["documents"]["rows"], config["documents"]["rows_rule"]
    return config


def _tiny_config(**changes):
    config = copy.deepcopy(_real())
    config.update(copy.deepcopy(TINY))
    config.update(changes)
    return config


def test_the_manifest_with_the_cell_is_sound():
    manifest = mf.load()
    assert mf.check(manifest) == []
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": NAME, "traffic": "ssgd_packed_1chip",
                    "chips": 1}
    for word in ("8,192", "packed", "log-normal", "median 512", "no padding",
                 "9 of 10", "8 fixed rows", "12/15/19/22/27/33/44/71 %",
                 "seeds draw ids only"):
        assert word in cell["why"], word
    assert len(cell["why"]) <= 200
    assert "/".join(str(int(share + 0.5)) for share in SHARES) + " %" in cell["why"]
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    # the metrics that came with the cell list it first; a later cell may
    # have joined a list behind it (PR 57 joined `pk_ffn_ms`')
    mine = [m for m in manifest["per_layer"] if m.get("workloads", [""])[0] == CELL]
    assert [{**m, "workloads": [CELL]} for m in mine] == [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": "step_ms_p50", "workloads": [CELL]}
        for name, unit, better, source, layer in MINE]
    assert sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", []) and m not in mine) == sorted(JOINED)
    # nine configurations and ten cells, one of them on four chips
    assert len(manifest["configs"]) >= 9 and len(manifest["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_configuration_is_the_catalogs_but_for_its_cut():
    manifest = mf.load()
    config = _real()
    differs = sorted(k for k, v in CATALOG.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["vocab_size"]) == (10, 12544)
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["vocab_size"] == 100352 == 8 * config["vocab_size"]
    # the published list is kept whole (as the Laguna file keeps its lists)
    # and the family runs its first ten: one whole period, nine to one
    assert config["layer_types"] == LAYER_TYPES and len(LAYER_TYPES) == 40
    kinds = granite_hybrid.layer_types(config)
    assert kinds == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert LAYER_TYPES.count("mamba") == 36 == 4 * kinds.count("mamba")
    assert [i for i, k in enumerate(LAYER_TYPES) if k == "attention"] == [5, 15, 25, 35]
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["source"] == config["source"] and entry["source"].endswith(
        "ibm-granite/granite-4.0-h-micro/blob/main/config.json")
    for word in ("three further chips", "eight ways", "772,160,448", "12.35e9",
                 "depth 10 of 40"):
        assert word in config["deployment"], word
    assert len(config["assumed"]) >= 10
    for word in ("8,192", "log-normal", "end-of-document", "seq_idx", "A_log",
                 "dt_bias", "normal(0, 0.02)", "mamba_chunk_size", "float32",
                 "recomputed_layer_types", "rope_theta", "gate first"):
        assert any(word in line for line in config["assumed"]), word
    assert _free()["documents"] == {
        "distribution": "lognormal", "median": 512, "sigma": 1.25,
        "shortest": 16, "longest": 8192, "end_of_document_id": 0}
    assert {k: v for k, v in config["documents"]["rows_rule"].items()
            if k != "what"} == {"seeds": 250, "batches": 8, "keep": 8}
    assert len(config["documents"]["rows"]) == 8
    assert config["sequence_length"] == 8192
    assert config["flash_blocks"] == [512, 512]
    assert (config["param_dtype"], config["compute_dtype"], config["head_dtype"]) == (
        "float32", "bfloat16", "float32")
    traffic = mf.cell(manifest, CELL)["traffic"]
    assert (traffic["per_chip_batch"], traffic["pool"], traffic["mesh"]) == (
        1, 8, {"dp": 1})
    assert (traffic["launcher"], traffic["step"], traffic["placement"]) == (
        "none", "ssgd", "shard_batch")
    assert traffic["optimizer"] == {"name": "adamw", "learning_rate": 0.0003}


def test_the_cut_holds_772_160_448_parameters():
    """ISSUE 52's count, by `eval_shape`: 76,182,976 in a Mamba-2 layer (W_in
    17.43 M, W_out 8.39 M, taps, conv bias, dt_bias, A_log, D, the gated
    norm, the feed-forward's 50.33 M, two norms), 60,821,504 in the attention
    layer, 25,690,112 in the tied embedding, once; 12.35e9 bytes at 16 a
    parameter."""
    state = jax.eval_shape(lambda: granite_hybrid.init(_real(), 0))

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    assert set(state) == {"embed", "ln_f_scale", "layers"}
    first, attention, second = state["layers"]  # 5 + 1 + 4 layers
    assert size(first) == 5 * 76_182_976 and size(second) == 4 * 76_182_976
    assert size(attention) == 60_821_504
    assert first["w_ssm_in"].shape == (5, 2048, 4096 + 4352 + 64)
    assert size(first["w_ssm_in"]) == 5 * 17_432_576
    assert first["wo"].shape == (5, 4096, 2048)
    assert first["conv_w"].shape == (5, 4, 4352) and first["conv_b"].shape == (5, 4352)
    assert first["ssm_norm_scale"].shape == (5, 4096)
    for stack in state["layers"]:  # both branches in every layer
        assert stack["w_gate"].shape[1:] == stack["w_up"].shape[1:] == (2048, 8192)
        assert stack["w_down"].shape[1:] == (8192, 2048)
        assert {k for k in stack if k.startswith("ln")} == {"ln1_scale", "ln2_scale"}
    assert (attention["wq"].shape, attention["wk"].shape, attention["wv"].shape,
            attention["wo"].shape) == ((1, 2048, 2048), (1, 2048, 512),
                                       (1, 2048, 512), (1, 2048, 2048))
    assert state["embed"].shape == (12544, 2048) and size(state["embed"]) == 25_690_112
    assert size(state) == 772_160_448
    assert 12.35e9 < 16 * size(state) < 12.36e9
    mc = granite_hybrid.model_config(_real())
    recomputed = _real()["recomputed_layer_types"]
    assert [(k.mixer, k.ffn, k.layer_remat, n) for k, n in mc.stacks] == [
        ("mamba2", "swiglu", "mamba" in recomputed, 5),
        ("attention", "swiglu", "attention" in recomputed, 1),
        ("mamba2", "swiglu", "mamba" in recomputed, 4)]
    assert mc.ssm_dims == (64, 64, 128, 1) and mc.conv_taps == 4
    assert (mc.n_heads, mc.kv_heads, mc.head_dim) == (32, 8, 64)
    assert (mc.embedding_multiplier, mc.attention_multiplier,
            mc.residual_multiplier, mc.logits_scaling) == (12.0, 1 / 64, 0.22, 8.0)
    assert (mc.positions, mc.tied_head, mc.norm_eps, mc.end_of_document) == (
        "none", True, 1e-5, 0)


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", False), ("hidden_act", "gelu"),
    ("attention_bias", True), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("num_local_experts", 8),
    ("position_embedding_type", "rope"), ("mamba_expand", 3)])
def test_the_family_refuses_a_layer_it_does_not_run(key, value):
    with pytest.raises(ValueError, match="as published"):
        granite_hybrid.model_config(_tiny_config(**{key: value}))


def test_layer_types_that_do_not_name_the_depths_layers_are_refused():
    for kinds in (["mamba", "attention"], ["mamba", "attention", "moe"]):
        with pytest.raises(ValueError, match="layer_types"):
            granite_hybrid.layer_types(_tiny_config(layer_types=kinds))


# --- operation and byte counts, by hand --------------------------------------

def test_scan_operations_and_bytes_by_hand():
    """One row of 128 tokens, 8 heads of 16 on one group's B and C of 16, at
    the configuration's chunk of 256: a position of the group the scores
    against its chunk, a position of a head their product with x, the
    chunk's state and its read-out; forward once and backward twice; the
    documents' numbers, 4 bytes a position each way, beside the arrays."""
    config = _tiny_config()
    a_position = 1 * (2 * 256 * 16) + 8 * (2 * 256 * 16 + 2 * 16 * 16 + 2 * 16 * 16)
    assert granite_hybrid.ssm_core_flops_per_sample(config) == 3 * a_position * 128
    assert granite_hybrid.ssm_core_bytes_per_sample(config) == 128 * (
        5 * 128 * 2 + 6 * 1 * 16 * 2 + 3 * 8 * 4 + 2 * 4)
    real = _real()
    assert granite_hybrid.ssm_core_flops_per_sample(real) == 3 * 8192 * (
        2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 128 * 64)) == pytest.approx(
            104.69e9, rel=1e-4)
    assert granite_hybrid.ssm_core_bytes_per_sample(real) == 8192 * (
        5 * 4096 * 2 + 6 * 128 * 2 + 3 * 64 * 4 + 8) == 354_484_224
    # on the v5e the operations bound this scan: 0.53 ms against 0.43 a layer
    peaks = harness.load_peaks("TPU v5 lite")
    t_flops = granite_hybrid.ssm_core_flops_per_sample(real) / peaks["bf16_flops"]
    t_bytes = granite_hybrid.ssm_core_bytes_per_sample(real) / peaks["hbm_bytes_per_s"]
    assert t_flops == pytest.approx(0.531e-3, rel=1e-2) and t_bytes < t_flops
    assert t_bytes == pytest.approx(0.433e-3, rel=1e-2)


def test_core_operations_follow_the_documents():
    """4 query heads on 2 key/value heads of 16: 2 products forward and 4
    backward over 16 features a seen pair, 2 operations a multiply-add; the
    pairs are those within a document."""
    config = _tiny_config()
    assert granite_hybrid.attn_core_flops(config, 1000) == 6 * 2 * 1000 * 4 * 16
    assert granite_hybrid.attn_core_bytes_per_sample(config) == 128 * (
        6 * (4 + 2) * 16 * 2 + 8)
    row = np.ones(129, np.int32)
    row[[9, 31, 32, 70]] = 0  # documents of 10, 22, 1, 38 and 57 positions
    assert granite_hybrid.row_documents(config, row[:-1]) == [10, 22, 1, 38, 57]
    pairs = 55 + 253 + 1 + 741 + 1653
    assert granite_hybrid.within_document_pairs(config, row[None]) == pairs
    assert granite_hybrid.causal_pairs(config) == 128 * 129 / 2
    # a row of one document is the whole causal sweep
    assert granite_hybrid.within_document_pairs(
        config, np.ones((1, 129), np.int32)) == granite_hybrid.causal_pairs(config)
    # blocks of 32: of the 10 visited, (1, 0) holds keys 0..31 and queries
    # 32..63, (2, 0), (3, 0) and (3, 1) likewise lie across a boundary whole
    assert granite_hybrid.dead_block_share(config, [row[None]]) == 0.4
    real = _real()
    assert granite_hybrid.attn_core_flops(real, 1.0) == 6 * 2 * 32 * 64


def test_the_expected_pairs_are_the_drawn_batches_mean():
    """The renewal argument of `_expected_pairs` against rows drawn by
    `host_batch`, at the tests' size and at the cell's: within three
    standard errors."""
    for config, rows, seeds in ((_tiny_config(), 64, 6), (_free(), 8, 24)):
        drawn = [granite_hybrid.within_document_pairs(
            config, granite_hybrid.host_batch(config, seed, i, rows)[j:j + 1])
            for seed in range(seeds) for i in range(2) for j in range(rows)]
        expected = granite_hybrid.expected_within_document_pairs(config)
        error = np.std(drawn) / np.sqrt(len(drawn))
        assert abs(np.mean(drawn) - expected) < 3 * error, (np.mean(drawn), expected)
    # at the cell's size: three tenths of a full causal sweep
    share = expected / granite_hybrid.causal_pairs(_real())
    assert share == pytest.approx(0.296, abs=2e-3)
    # documents of the row's own length, 8,192 each: a row cut from the
    # stream's middle begins a uniform way into one, so position i is at
    # least its document's (a + 1)-th with 1 - a / 8192, and the sum over a
    # <= i and over i is S (S + 1) / 2 - (S - 1) (S + 1) / 6
    whole = dict(_real()["documents"], shortest=8192, longest=8192)
    assert granite_hybrid.expected_within_document_pairs(
        {**_real(), "documents": whole}) == pytest.approx(
            8192 * 8193 / 2 - 8191 * 8193 / 6, rel=1e-9)


def test_flops_per_sample_by_hand():
    """Per token: a Mamba-2 layer (W_in 64 x (128 + 160 + 8), 4 taps over 160
    channels, W_out 128 x 64) and its feed-forward (3 x 64 x 96), twice; the
    attention layer (W_q and W_o 64 x 64, W_k and W_v 64 x 32) and its
    feed-forward; the tied matrix once, 320 x 64; 2 operations a
    multiply-add, x 3 for forward and backward; the two scans, and the core's
    pairs in expectation."""
    config = _tiny_config()
    ffn = 3 * 64 * 96
    mamba = 64 * (128 + 160 + 8) + 4 * 160 + 128 * 64 + ffn
    attention = 2 * 64 * 64 + 2 * 64 * 32 + ffn
    assert granite_hybrid.ffn_params_per_token(config) == ffn
    assert granite_hybrid.layer_params_per_token(config, "mamba") == mamba
    assert granite_hybrid.layer_params_per_token(config, "attention") == attention
    params = 320 * 64 + 2 * mamba + attention
    assert granite_hybrid.matmul_params_per_token(config) == params
    pairs = granite_hybrid.expected_within_document_pairs(config)
    assert granite_hybrid.flops_per_sample(config) == (
        3 * 2 * params * 128
        + 2 * granite_hybrid.ssm_core_flops_per_sample(config)
        + 6 * 2 * pairs * 4 * 16)
    real = _real()
    # the issue's arithmetic: 503 M of 772 M in the feed-forwards, about 40
    # TFLOP a step, attention under 2 % of it
    assert 10 * granite_hybrid.ffn_params_per_token(real) == 503_316_480
    assert granite_hybrid.matmul_params_per_token(real) == 772_039_680
    assert granite_hybrid.flops_per_sample(real) == pytest.approx(39.13e12, rel=1e-3)
    core = granite_hybrid.attn_core_flops(
        real, granite_hybrid.expected_within_document_pairs(real))
    assert core / granite_hybrid.flops_per_sample(real) < 0.02
    scans = 9 * granite_hybrid.ssm_core_flops_per_sample(real)
    assert scans / granite_hybrid.flops_per_sample(real) == pytest.approx(0.024, abs=2e-3)


def test_the_multiplying_parameters_are_the_initialised_trees():
    """Every matrix of the initialised tree multiplies every token once (the
    tied one as the head's product; the lookup is none): the family's count
    from the configuration against the tree's own leaves."""
    real = _real()
    state = jax.eval_shape(lambda: granite_hybrid.init(real, 0))
    matrices = sum(
        x.size for path, x in jax.tree_util.tree_leaves_with_path(state)
        if not jax.tree_util.keystr(path).rstrip("']").endswith(
            ("_scale", "conv_b", "dt_bias", "A_log", "D_skip")))
    assert granite_hybrid.matmul_params_per_token(real) == matrices


def test_host_batches_are_packed_documents_from_the_seed():
    config = _free()
    a = granite_hybrid.host_batch(config, 2**31 + 11, 3, 2)
    b = granite_hybrid.host_batch(config, 2**31 + 11, 3, 2)
    c = granite_hybrid.host_batch(config, 2**31 + 12, 3, 2)
    assert a.shape == (2, 8193) and a.dtype == np.int32  # S + 1 ids
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < 12544
    # ids uniform over the rows that are not the end-of-document id
    body = a[a != 0]
    assert np.bincount(body, minlength=12544)[1:].max() < 12
    rows = [granite_hybrid.host_batch(config, seed, i, 1)[0, :-1]
            for seed in range(12) for i in range(8)]
    documents = [granite_hybrid.row_documents(config, row) for row in rows]
    assert all(sum(d) == 8192 for d in documents)
    counts = [len(d) for d in documents]
    assert 7 < np.mean(counts) < 10.5 and min(counts) >= 1
    # whole documents (neither a row's head nor its tail) keep to the clip,
    # and their median is the log-normal's
    whole = [l for d in documents for l in d[1:-1]]
    assert min(whole) >= 16 and max(whole) <= 8192
    assert 400 < np.median(whole) < 640
    # the rows of one batch are one stream cut: the second row goes on where
    # the first ended
    stream = granite_hybrid.host_batch(config, 5, 0, 2)
    assert stream.shape == (2, 8193)
    assert not np.array_equal(stream[0], stream[1])


# --- the cell's pool: the rows the configuration's file writes (PR 60) -------

SHARES = (11.66, 15.15, 18.61, 22.16, 26.50, 33.03, 44.05, 70.68)  # % of pairs


@pytest.mark.parametrize("seed", [0, 1, 2, 2**31 + 11])
def test_the_pools_boundaries_are_the_files_under_every_seed(seed):
    """Batches 0 to 7 of one row, the traffic's pool, as `harness.measure`
    and `pool(record)` make them: the file's pieces whatever the seed, and
    ids that follow the seed."""
    config = _real()
    rows = config["documents"]["rows"]
    pool = [granite_hybrid.host_batch(config, seed, i, 1) for i in range(8)]
    other = [granite_hybrid.host_batch(config, seed + 1, i, 1) for i in range(8)]
    for i, (batch, again) in enumerate(zip(pool, other)):
        assert batch.shape == (1, 8193) and batch.dtype == np.int32
        assert granite_hybrid.row_documents(config, batch[0]) == rows[i]
        assert ((batch == 0) == (again == 0)).all()  # one set of boundaries
        assert (batch != again).mean() > 0.99  # and other ids
        assert (batch == granite_hybrid.host_batch(config, seed, i, 1)).all()
    # the batches of one seed are not one another's ids under other cuts
    assert (pool[0] != pool[1]).mean() > 0.99
    record = {"workload": CELL, "seed": seed, "samples_per_step": 1}
    assert all((a == b).all() for a, b in zip(granite_hybrid.pool(record), pool))


@pytest.mark.parametrize("i", range(8))
def test_a_written_row_is_whole(i):
    """Pieces of 1 to 8,192 ids that sum to the 8,193 a row holds; no
    position is padding: the end-of-document id ends every piece but the
    last and stands nowhere else, every other id is of rows 1 to 12,543,
    uniform; the share of the row's causal pairs within a document is the
    one the rule's note states (and, rounded, the cell's `why`)."""
    config = _real()
    pieces = config["documents"]["rows"][i]
    assert sum(pieces) == 8193 and 1 <= min(pieces) and max(pieces) <= 8192
    row = granite_hybrid.host_batch(config, 2**31 + 5, i, 1)[0]
    assert (np.flatnonzero(row == 0) == np.cumsum(pieces)[:-1] - 1).all()
    assert 1 <= row[row != 0].min() and row.max() < 12544
    assert np.bincount(row, minlength=12544)[1:].max() < 9
    share = 100 * granite_hybrid.within_document_pairs(
        config, row[None]) / granite_hybrid.causal_pairs(config)
    assert share == pytest.approx(SHARES[i], abs=0.005)
    assert f"{SHARES[i]:.2f}" in config["documents"]["rows_rule"]["what"]


def test_the_rule_run_again_gives_the_files_rows():
    """2,000 rows of the free generator, ranked by the attention layer's
    work, the middle of each eighth: `rows_by_rule` reads the rule's numbers
    from the file and draws as a configuration without rows does."""
    config = _real()
    rows = granite_hybrid.rows_by_rule(config)
    assert rows == config["documents"]["rows"] and len(rows) == 8
    # by hand: the 0-based ranks 125, 375, ..., 1,875 of the 2,000
    free = _free()
    drawn = [granite_hybrid.host_batch(free, seed, i, 1)
             for seed in range(250) for i in range(8)]
    pairs = [granite_hybrid.within_document_pairs(free, b) for b in drawn]
    ranked = sorted(range(2000), key=lambda k: (pairs[k], k))
    assert [granite_hybrid.row_documents(free, drawn[ranked[r]][0])
            for r in range(125, 2000, 250)] == rows
    # the fourth and fifth rows stand on either side of the draws' median,
    # the last at their 94th percentile
    assert pairs[ranked[875]] < np.median(pairs) < pairs[ranked[1125]]
    assert np.mean(np.array(pairs) < pairs[ranked[1875]]) == pytest.approx(0.9375)
    for word in ("2,000", "within_document_pairs", "125, 375, ..., 1,875", "8,193",
                 "same under every seed", "rows_by_rule"):
        assert word in config["documents"]["rows_rule"]["what"], word


def test_the_pools_mean_pairs_are_the_expectations():
    """`flops_per_sample` and `mfu_pct` count the attention layer by the
    expectation under the documents' distribution; the pool every run now
    cycles has that mean to 2 % (a seeded pool's lay between 20 and 40 % of
    the causal pairs, ISSUE 60)."""
    config = _real()
    record = {"workload": CELL, "seed": 7, "samples_per_step": 1}
    mean = granite_hybrid.pool_within_document_pairs(record)
    expected = granite_hybrid.expected_within_document_pairs(config)
    assert abs(mean / expected - 1) < 0.03
    assert 100 * mean / granite_hybrid.causal_pairs(config) == pytest.approx(
        np.mean(SHARES), abs=0.005) == pytest.approx(30.23, abs=0.005)
    assert granite_hybrid.pool_within_document_pairs({**record, "seed": 8}) == mean


@pytest.mark.parametrize("i,n", [(harness.SAMPLE_INDEX, 1), (8, 1), (0, 2), (3, 2)],
                         ids=["the_sample", "past_the_rows", "two_rows", "two_rows_later"])
def test_what_is_not_the_pool_still_follows_the_seed(i, n):
    """The reference's sample, a batch past the written rows and a batch of
    several rows draw their documents from the seed, exactly as a
    configuration without `rows` does: `correct` keeps meeting layouts it
    has not seen."""
    config, free = _real(), _free()
    a = granite_hybrid.host_batch(config, 11, i, n)
    assert (a == granite_hybrid.host_batch(free, 11, i, n)).all()
    b = granite_hybrid.host_batch(config, 12, i, n)
    assert a.shape == b.shape == (n, 8193)
    assert [granite_hybrid.row_documents(config, r) for r in a] != [
        granite_hybrid.row_documents(config, r) for r in b]
    assert granite_hybrid.row_documents(config, a[0]) not in config["documents"]["rows"]


def test_a_configuration_without_rows_draws_every_batch():
    """The tests' small configurations and the cell's before PR 60."""
    free = _free()
    for i in range(8):
        a, b = (granite_hybrid.host_batch(free, seed, i, 1) for seed in (0, 1))
        assert granite_hybrid.row_documents(free, a[0]) != (
            granite_hybrid.row_documents(free, b[0]))
    tiny = _tiny_config()
    assert "rows" not in tiny["documents"]
    assert granite_hybrid.host_batch(tiny, 0, 0, 1).shape == (1, 129)


@pytest.mark.parametrize("pieces", [[4096, 4096], [8193, 0], [8000, 100, 94]],
                         ids=["short", "an_empty_piece", "long"])
def test_a_written_row_that_is_not_a_row_is_refused(pieces):
    config = _real()
    config["documents"]["rows"][2] = pieces
    with pytest.raises(ValueError, match=r"documents.rows\[2\]"):
        granite_hybrid.host_batch(config, 0, 2, 1)
    granite_hybrid.host_batch(config, 0, 1, 1)


def test_the_words_say_what_the_traffic_now_is():
    manifest = mf.load()
    cell = mf.cell(manifest, CELL)
    for word in ("documents.rows", "same boundaries under every seed",
                 "the seed draws the ids", "11.7 to 70.7 %"):
        assert word in cell["traffic"]["what"], word
    assert any("documents.rows_rule" in line and "same under every seed" in line
               for line in cell["config"]["assumed"])
    for word in ("documents` holds `rows`", "SAMPLE_INDEX", "ids alone"):
        assert word in granite_hybrid.host_batch.__doc__, word


# --- the program against the reference --------------------------------------

def _both(dtype, seed=5):
    config = _tiny_config(compute_dtype=dtype)
    state = granite_hybrid.init(config, seed)
    sample = granite_hybrid.host_batch(config, seed, 0, 2)
    got = granite_hybrid.program_loss_and_grads(config)(state, sample)
    want = granite_hybrid.reference_loss_and_grads(config, state, sample)
    return config, state, sample, got, want


def test_reference_equals_program_in_float32():
    config, _, sample, (loss, grads), (ref_loss, ref_grads) = _both("float32")
    assert all(len(granite_hybrid.row_documents(config, row[:-1])) >= 2
               for row in sample)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    assert harness.relative_error(grads, ref_grads) <= 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)


def test_bfloat16_program_is_within_the_familys_tolerances():
    _, _, _, (loss, grads), (ref_loss, ref_grads) = _both("bfloat16")
    assert abs(float(loss) - float(ref_loss)) <= (
        granite_hybrid.LOSS_RTOL * abs(float(ref_loss)))
    error = harness.relative_error(grads, ref_grads)
    assert 1e-4 < error <= granite_hybrid.GRAD_RTOL, error
    assert 0 < granite_hybrid.LOSS_RTOL < granite_hybrid.GRAD_RTOL < 0.1


def test_the_reference_computes_in_blocks_what_it_computes_at_once():
    config = _tiny_config(compute_dtype="float32")
    state = granite_hybrid.init(config, 3)
    sample = granite_hybrid.host_batch(config, 3, 0, 1)
    from benchmark.reference import granite_hybrid as reference

    whole = dict(granite_hybrid._hyper(config), query_block=128, position_block=128)
    at_once = reference.loss_and_grads(state, sample, **whole)
    in_blocks = reference.loss_and_grads(
        state, sample, **{**whole, "query_block": 32, "position_block": 16})
    assert float(at_once[0]) == pytest.approx(float(in_blocks[0]), rel=1e-6)
    assert harness.relative_error(in_blocks[1], at_once[1]) <= 1e-5


def test_the_reference_imports_nothing_of_the_program_or_of_another_reference():
    import benchmark.reference.granite_hybrid as reference

    with open(reference.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports
                            if "kungfu_tpu" in line or "benchmark" in line]
    assert "pallas" not in text and "custom_vjp" not in text
    assert 'default_matmul_precision("highest")' in text
    assert granite_hybrid.REFERENCE_SAMPLES == 1


def test_the_cells_program_holds_to_its_declared_precision():
    config = _tiny_config()
    state = jax.eval_shape(lambda: granite_hybrid.init(config, 0))
    sample = granite_hybrid.host_batch(config, 0, 0, granite_hybrid.REFERENCE_SAMPLES)
    traced = granite_hybrid.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, granite_hybrid.head_width(config),
                                    traced.jaxpr, state, state) == []
    low = _tiny_config(param_dtype="bfloat16")
    assert harness.precision_faults(low, granite_hybrid.head_width(low),
                                    traced.jaxpr, state, state)


def test_the_real_program_holds_to_its_declared_precision():
    """At the published widths, from shapes alone: no matmul or reduction
    over the head's 12,544 rows is in bfloat16, and no other array of the
    step has that width."""
    config = _real()
    state = jax.eval_shape(lambda: granite_hybrid.init(config, 0))
    sample = granite_hybrid.host_batch(config, 0, 0, granite_hybrid.REFERENCE_SAMPLES)
    traced = granite_hybrid.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, granite_hybrid.head_width(config),
                                    traced.jaxpr, state, state) == []


# --- the whole of measure ----------------------------------------------------

@pytest.fixture(scope="module")
def events():
    return harness.EventCounter()


def test_measure_at_tiny_size_on_two_cpu_devices(events):
    """State, pool, first step, warm-up, probe, window, checks and the
    reference, on a dp = 2 mesh of virtual CPU devices, every batch packed."""
    from kungfu_tpu.parallel import make_mesh

    m = mf.load()
    cell = mf.cell(m, CELL)
    cell["config"] = _tiny_config()
    cell["traffic"].update(per_chip_batch=2, mesh={"dp": 2})
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    record = harness.measure(cell, mesh, OneProcess(), {"bf16_flops": 197e12},
                             seed=2**31 + 7, seconds=0.3, trace_dir=None,
                             events=events, t_command=time.time(),
                             marks=child_marks())
    assert record["checks"]["no_compile_in_window"], record["window"]["compiles"]
    assert record["checks"]["loss_fell"], (record["losses_before"],
                                           record["window"]["losses"][-8:])
    assert record["correct"], (record["checks"], record["reference"])
    assert record["reference"]["precision_faults"] == []
    assert record["failed"] == 0
    assert record["samples_per_step"] == 4 and record["chips"] == 2
    assert record["flops_per_sample"] == granite_hybrid.flops_per_sample(cell["config"])
    json.dumps(record)
    assert all(v > 0 for v in end_to_end.values(record).values())
    with pytest.raises(RuntimeError, match="chip runs only"):
        end_to_end.result_line(record, None, m)


# --- the readers on a drawn trace ---------------------------------------

MS = 8_000_000  # a unit of the drawing below, in ns: 8 ms
# Two steps of 60 units on one chip, each alike:
#   segments [0, 0.5)  ssm.in [0.5, 3)  conv [3, 4)  marks [4, 4.5)
#   scan.fwd [4.5, 6)  ssm.norm [6, 7)  ssm.out [7, 8)  ffn.fwd [8, 12)
#   qkv [12, 13)  core.fwd [13, 15)  wo [15, 16)  head [16, 19)
#   head.bwd [19, 23)  ffn.bwd [23, 31)  core.bwd [31, 35)  qkv.bwd [35, 37)
#   ssm.out.bwd [37, 39)  ssm.norm.bwd [39, 40)  scan.bwd [40, 45)
#   dqk.sum [45, 45.5)  conv.bwd [45.5, 47)  ssm.in.bwd [47, 53)
#   adamw [53, 56) (under `optimizer`)  stray [56, 56.5) (no scope)
STEP_OPS = [("segments", 0, 0.5), ("ssm.in", 0.5, 3), ("conv", 3, 4),
            ("marks", 4, 4.5), ("scan.fwd", 4.5, 6), ("ssm.norm", 6, 7),
            ("ssm.out", 7, 8), ("ffn.fwd", 8, 12), ("qkv", 12, 13),
            ("core.fwd", 13, 15), ("wo", 15, 16), ("head", 16, 19),
            ("head.bwd", 19, 23), ("ffn.bwd", 23, 31), ("core.bwd", 31, 35),
            ("qkv.bwd", 35, 37), ("ssm.out.bwd", 37, 39),
            ("ssm.norm.bwd", 39, 40), ("scan.bwd", 40, 45),
            ("dqk.sum", 45, 45.5), ("conv.bwd", 45.5, 47),
            ("ssm.in.bwd", 47, 53), ("adamw", 53, 56), ("stray", 56, 56.5)]
DRAWN = {
    "chips": [{"plane": "/device:TPU:0", "program": "jit_step",
               "steps": [[0, 60 * MS], [60 * MS, 120 * MS]],
               "ops": [[name, int((at + a) * MS), int((at + b) * MS)]
                       for at in (0, 60) for name, a, b in STEP_OPS]}],
    "host": [], "lines": {},
}
FWD = "jit(step)/shard_map/jvp()/while/body/closed_call"
BWD = "jit(step)/shard_map/transpose(jvp())/while/body/closed_call/checkpoint"
SCOPES = {
    "segments": "jit(step)/shard_map/jvp()/segments/cumsum",
    "ssm.in": f"{FWD}/ssm/ssm_proj/dot_general",
    "conv": f"{FWD}/ssm/ssm_conv/mul",
    "marks": f"{FWD}/ssm/ssm_core/concatenate",
    "scan.fwd": f"{FWD}/ssm/ssm_core/ssm_scan_forward/pallas_call",
    "ssm.norm": f"{FWD}/ssm/ssm_norm/rsqrt",
    "ssm.out": f"{FWD}/ssm/ssm_proj/dot_general",
    "ffn.fwd": f"{FWD}/ffn/dot_general",
    "qkv": f"{FWD}/attn/dot_general",
    "core.fwd": f"{FWD}/attn/attn_full/attn_core/pallas_call",
    "wo": f"{FWD}/attn/dot_general",
    "head": "jit(step)/shard_map/jvp(head_loss)/dot_general",
    "head.bwd": "jit(step)/shard_map/transpose(jvp(head_loss))/dot_general",
    "ffn.bwd": f"{BWD}/ffn/dot_general",
    "core.bwd": f"{BWD}/attn/attn_full/attn_core/pallas_call",
    "qkv.bwd": f"{BWD}/attn/dot_general",
    "ssm.out.bwd": f"{BWD}/ssm/ssm_proj/dot_general",
    "ssm.norm.bwd": f"{BWD}/ssm/ssm_norm/mul",
    "scan.bwd": f"{BWD}/ssm/ssm_core/ssm_scan_backward/pallas_call",
    "dqk.sum": f"{BWD}/ssm/ssm_core/reduce_sum",
    "conv.bwd": f"{BWD}/ssm/ssm_conv/mul",
    "ssm.in.bwd": f"{BWD}/ssm/ssm_proj/dot_general",
    "adamw": "jit(step)/shard_map/optimizer/optimizer_update/add",
}
SEED = 2**31 + 3


def _record(samples_per_step=1):
    return {"workload": CELL, "scopes": SCOPES, "seed": SEED,
            "samples_per_step": samples_per_step,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_drawn_times():
    record = _record()
    # both kernels, the marks made for them and the sum of the blocks' dq, dk
    assert pk_ssm_core_ms.read(record, DRAWN) == pytest.approx(8 * (0.5 + 1.5 + 5 + 0.5))
    # `ssm` less the scan: projections, convolution, the gated norm
    assert pk_ssm_mix_ms.read(record, DRAWN) == pytest.approx(
        8 * (2.5 + 1 + 1 + 1 + 2 + 1 + 1.5 + 6))
    assert pk_attn_core_ms.read(record, DRAWN) == pytest.approx(8 * (2 + 4))
    assert pk_ffn_ms.read(record, DRAWN) == pytest.approx(8 * (4 + 8))
    assert pk_segments_ms.read(record, DRAWN) == pytest.approx(8 * 0.5)


def test_drawn_shares_follow_the_runs_own_documents():
    """At the real widths: one packed row a step, nine scans bound by their
    operations, and one core whose required operations are those of the
    pairs within a document of the run's own pool, made again from its seed."""
    record = _record()
    real = _real()
    peaks = harness.load_peaks("TPU v5 lite")
    scans = 9 * granite_hybrid.ssm_core_flops_per_sample(real) / peaks["bf16_flops"]
    assert pk_ssm_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * scans / 60e-3)
    pool = [granite_hybrid.host_batch(real, SEED, i, 1) for i in range(8)]
    pairs = np.mean([granite_hybrid.within_document_pairs(real, b) for b in pool])
    assert granite_hybrid.pool_within_document_pairs(record) == pytest.approx(pairs)
    core = max(6 * 2 * pairs * 32 * 64 / peaks["bf16_flops"],
               granite_hybrid.attn_core_bytes_per_sample(real) / peaks["hbm_bytes_per_s"])
    assert pk_attn_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * core / 48e-3)
    share = pk_within_doc_pairs_pct.read(record, None)
    assert share == pytest.approx(100 * pairs / (8192 * 8193 / 2))
    assert share == pytest.approx(30.23, abs=0.005)
    # another seed is other ids in the same documents (PR 60)
    other = pk_within_doc_pairs_pct.read({**record, "seed": SEED + 1}, None)
    assert other == share
    assert 0 < pk_ssm_core_roofline_pct.read(record, DRAWN) < 100
    assert 0 < pk_attn_core_roofline_pct.read(record, DRAWN) < 100


def test_the_programs_account_of_the_pool_is_the_readers():
    """`pk_within_doc_pairs_pct` is counted by the benchmark (numpy, in a
    process without jax); the program's `packing_stats` of the same batches
    says the same."""
    real = _real()
    record = _record()
    pool = granite_hybrid.pool(record)
    assert len(pool) == 8 and pool[0].shape == (1, 8193)
    stats = [granite_hybrid.packing_stats(real, batch) for batch in pool]
    share = np.mean([s["within_document_pairs"][0] for s in stats])
    assert pk_within_doc_pairs_pct.read(record, None) == pytest.approx(
        100 * share, rel=1e-5)
    assert [s["documents"][0] for s in stats] == [
        len(granite_hybrid.row_documents(real, b[0, :-1])) for b in pool]


DEVICE_READERS = (pk_ssm_core_ms, pk_ssm_core_roofline_pct, pk_ssm_mix_ms,
                  pk_attn_core_ms, pk_attn_core_roofline_pct, pk_ffn_ms,
                  pk_segments_ms)
READERS = DEVICE_READERS + (pk_within_doc_pairs_pct,)


@pytest.mark.parametrize("reader", DEVICE_READERS,
                         ids=lambda r: r.__name__.split(".")[-1])
def test_readers_find_nothing_without_a_trace_or_a_scope_table(reader):
    assert reader.read(_record(), None) is None
    assert reader.read(_record(), {"chips": [], "host": [], "lines": {}}) is None
    for scopes in (None, {}):
        assert reader.read({**_record(), "scopes": scopes}, DRAWN) is None
    assert reader.read({"workload": CELL}, DRAWN) is None


@pytest.mark.parametrize("reader", DEVICE_READERS,
                         ids=lambda r: r.__name__.split(".")[-1])
def test_a_program_without_the_scope_reads_nothing_run(reader):
    """A scope table that names none of the scopes (a step of another
    family, or of the parent commit): nothing ran under them, 0, and no
    share of any roof; nothing raises."""
    record = {**_record(), "scopes": {"head": SCOPES["head"]}}
    assert reader.read(record, DRAWN) == 0.0


def test_the_traced_line_holds_exactly_the_cells_metrics():
    manifest = mf.load()
    record = {**_record(), "traced": True, **drawn_setup(), "chips": 1,
              "window": {"compiles": 0, "t_done": [1.0, 1.4, 1.8, 2.2],
                         "spans": [["bench.input", 1.0, 1.001]]},
              "program_memory": {"total_bytes": 15_700_000_000},
              "memory_stats_peak_bytes": 1, "correct": True, "attempted": 20,
              "failed": 0}
    line = end_to_end.result_line(record, DRAWN, manifest)
    mine = {x["name"] for x in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(line["metrics"]) == mine
    assert {r.__name__.split(".")[-1] for r in READERS} <= mine
    assert set(JOINED) <= mine
    assert not {"ssm_core_ms", "ssm_mix_ms", "nope_core_ms", "moe_ms",
                "flash_core_ms", "full_core_ms", "loop_ffn_ms"} & mine
    assert line["metrics"]["optimizer_ms"]["value"] == pytest.approx(8 * 3.0)
    assert line["metrics"]["head_loss_ms"]["value"] == pytest.approx(8 * (3 + 4))
    assert line["metrics"]["pk_ssm_core_ms"]["value"] == pytest.approx(8 * 7.5)
    assert line["metrics"]["pk_ssm_core_roofline_pct"]["unit"] == "%"
    assert mf.check_result_line(line, manifest, CELL, traced=True) == []
