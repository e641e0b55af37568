"""The Nemotron-H family, its configuration and its six readers (PR 43): the
whole of `harness.measure` at tiny size on the CPU mesh, the parameter,
operation and byte counts against the initialised tree and sums made by hand,
the batches, the readers against a drawn trace, and the configuration file
against the catalog's numbers.

These tests find the cell and its entries by name, wherever later cells put
them: no position in the manifest is pinned."""

import copy
import json
import time

import jax
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.families import nemotron_h
from benchmark.launchers.none import OneProcess
from benchmark.layer_metrics import (moe_relu2_ms, nope_core_ms,
                                     nope_core_roofline_pct, ssm_core_ms,
                                     ssm_core_roofline_pct, ssm_mix_ms)
from drawn_setup import child_marks, drawn_setup

CELL = "nemotron_3_nano_30b_a3b.ssgd_ssm_8k_1chip"
NAME = "nemotron_3_nano_30b_a3b"
# the six metrics the cell brought, and the older lists it joined: the step's
# parts, which tests/benchmark/test_bench_setup.py wants of every transformer
# cell
MINE = (("ssm_core_ms", "ms", "lower", "Kernels"),
        ("ssm_core_roofline_pct", "%", "higher", "Kernels"),
        ("ssm_mix_ms", "ms", "lower", "Model"),
        ("nope_core_ms", "ms", "lower", "Kernels"),
        ("nope_core_roofline_pct", "%", "higher", "Kernels"),
        ("moe_relu2_ms", "ms", "lower", "Model"))
JOINED = ("optimizer_ms", "head_loss_ms")

# every mechanism on, at the tests' size (tests/test_nemotron_h.py); the
# kernel in interpret mode by a key of the configuration
TINY = dict(hidden_size=64, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=64, num_hidden_layers=5,
            hybrid_override_pattern="MEM*E", num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
            mamba_head_dim=8, ssm_state_size=16, n_groups=2,
            n_routed_experts=4, first_expert_held=4,
            published={"n_routed_experts": 16}, num_experts_per_tok=3,
            vocab_size=320, sequence_length=64, flash_blocks=[32, 32],
            flash_interpret=True)  # 320: no layer's width

# nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16's config.json as the catalog has it
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688, "hybrid_override_pattern": PATTERN,
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "n_group": 1, "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


def _real():
    return mf.cell(mf.load(), CELL)["config"]


def _tiny_config(**changes):
    config = copy.deepcopy(_real())
    config.update(TINY)
    config.update(changes)
    return config


def test_the_manifest_with_the_cell_is_sound():
    manifest = mf.load()
    assert mf.check(manifest) == []
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": NAME, "traffic": "ssgd_ssm_8k_1chip",
                    "chips": 1}
    for word in ("8,192", "Mamba-2", "1/16", "16x", "9/52"):
        assert word in cell["why"], word
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == [
        {"name": name, "unit": unit, "better": better, "source": "device_trace",
         "layer": layer, "moves": "step_ms_p50", "workloads": [CELL]}
        for name, unit, better, layer in MINE]
    assert sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", []) and m not in mine) == sorted(JOINED)
    # eight cells or more: a quarter of them may take four chips
    assert len(manifest["workloads"]) >= 8


def test_the_configuration_is_the_catalogs_but_for_its_cut():
    manifest = mf.load()
    config = _real()
    differs = sorted(k for k, v in CATALOG.items() if config.get(k) != v)
    # the pattern is cut with the depth: its first nine letters
    assert differs == sorted(config["reduced"] + ["hybrid_override_pattern"])
    assert sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (9, 8, 16384)
    assert config["hybrid_override_pattern"] == PATTERN[:9] == "MEMEM*EME"
    assert config["published"] == {
        **{k: CATALOG[k] for k in config["reduced"]},
        "hybrid_override_pattern": PATTERN}
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["n_routed_experts"] * 16 == CATALOG["n_routed_experts"]
    kinds = nemotron_h.layer_types(config)
    assert (kinds.count("M"), kinds.count("E"), kinds.count("*")) == (4, 4, 1)
    # nine is the longest period of the pattern: the most layers between two
    # attention layers, and one of the two
    assert max(len(run) for run in PATTERN.split("*")[1:-1]) + 1 == 9
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["source"] == config["source"] and entry["source"].endswith(
        "nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert "16 chips" in config["deployment"] and len(config["assumed"]) >= 10
    for word in ("position", "normal(0, 0.02)", "A_log", "dt_bias", "chunk",
                 "gate first", "rescale_prenorm_residual", "bias", "uniform",
                 "8,192", "recomputed", "convolution"):
        assert any(word in line for line in config["assumed"]), word
    assert config["sequence_length"] == 8192
    assert config["routers_trained"] is False
    assert config["flash_blocks"] == [512, 512]
    assert (config["param_dtype"], config["compute_dtype"], config["head_dtype"]) == (
        "float32", "bfloat16", "float32")
    traffic = mf.cell(manifest, CELL)["traffic"]
    assert (traffic["per_chip_batch"], traffic["pool"], traffic["mesh"]) == (
        1, 8, {"dp": 1})
    assert (traffic["launcher"], traffic["step"], traffic["placement"]) == (
        "none", "ssgd", "shard_batch")
    # ISSUE 43's: a constant rate from the initial parameters
    assert traffic["optimizer"] == {"name": "adamw", "learning_rate": 0.0003}


def test_the_cut_holds_666_963_456_parameters():
    """ISSUE 43's count, by `eval_shape`: 38,744,896 in a Mamba-2 layer (W_in
    27.70 M, W_out 11.01 M, taps, conv bias, dt_bias, A_log, D, two norms),
    23,399,040 in the attention layer, 100,125,440 in an expert layer (79.82 M
    held experts, 19.96 M shared, 0.34 M router, 128 biases), 2 x 44,040,192
    in embedding and head; 10.67e9 bytes at 16 a parameter."""
    state = jax.eval_shape(lambda: nemotron_h.init(_real(), 0))

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    assert len(state["layers"]) == 9  # nine stacks of one layer each
    by_kind = {}
    for kind, stack in zip("MEMEM*EME", state["layers"]):
        by_kind.setdefault(kind, set()).add(size(stack))
    assert by_kind == {"M": {38_744_896}, "*": {23_399_040}, "E": {100_125_440}}
    mamba, experts, attention = (state["layers"][i] for i in (0, 1, 5))
    assert mamba["w_ssm_in"].shape == (1, 2688, 4096 + 6144 + 64)
    assert size(mamba["w_ssm_in"]) == 2688 * 10304 == pytest.approx(27.70e6, rel=2e-4)
    assert mamba["wo"].shape == (1, 4096, 2688)
    assert mamba["conv_w"].shape == (1, 4, 6144) and mamba["conv_b"].shape == (1, 6144)
    assert mamba["ssm_norm_scale"].shape == (1, 4096)
    assert {k for k in mamba if k.startswith("ln")} == {"ln1_scale"}
    assert size({k: experts[k] for k in ("w_up", "w_down")}) == (
        8 * 2 * 2688 * 1856) == pytest.approx(79.82e6, rel=1e-4)
    assert size({k: experts[k] for k in ("shared_up", "shared_down")}) == (
        2 * 2688 * 3712) == pytest.approx(19.96e6, rel=3e-4)
    assert experts["router"].shape == (1, 2688, 128)
    assert experts["router_bias"].shape == (1, 128)
    assert {k for k in experts if k.startswith("ln")} == {"ln2_scale"}
    assert "w_gate" not in experts and "shared_gate" not in experts
    assert (attention["wq"].shape, attention["wk"].shape) == (
        (1, 2688, 4096), (1, 2688, 256))
    assert size(state["embed"]) == size(state["lm_head"]) == 16384 * 2688 == 44_040_192
    assert "pos_embed" not in state
    assert size(state) == 666_963_456
    assert 10.67e9 < 16 * size(state) < 10.68e9
    mc = nemotron_h.model_config(_real())
    recomputed = _real()["recomputed_layer_types"]
    assert [(k.mixer, k.ffn, k.layer_remat, n) for k, n in mc.stacks] == [
        {"M": ("mamba2", "none", "mamba" in recomputed, 1),
         "E": ("none", "moe", "moe" in recomputed, 1),
         "*": ("attention", "none", "attention" in recomputed, 1)}[kind]
        for kind in "MEMEM*EME"]
    assert mc.experts_held == (0, 8) and mc.n_experts == 128 and mc.top_k == 6
    assert mc.ssm_dims == (64, 64, 128, 8) and mc.conv_taps == 4
    assert (mc.n_heads, mc.kv_heads, mc.head_dim) == (32, 2, 128)
    assert (mc.router_scores, mc.router_bias, mc.gates, mc.routed_scale) == (
        "sigmoid", True, "renorm", 2.5)
    assert (mc.positions, mc.expert_act, mc.norm_eps) == ("none", "relu2", 1e-5)


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("mlp_hidden_act", "silu"),
    ("norm_topk_prob", False), ("use_conv_bias", False), ("n_group", 8),
    ("n_shared_experts", 2), ("mamba_proj_bias", True), ("attention_bias", True),
    ("sliding_window", 4096), ("mamba_hidden_act", "gelu"), ("mlp_bias", True)])
def test_the_family_refuses_a_layer_it_does_not_run(key, value):
    with pytest.raises(ValueError, match="as published"):
        nemotron_h.model_config(_tiny_config(**{key: value}))


def test_a_pattern_that_is_not_the_depths_letters_is_refused():
    for pattern in ("MEM*", "MEM*EM", "MEMAE"):
        with pytest.raises(ValueError, match="hybrid_override_pattern"):
            nemotron_h.layer_types(_tiny_config(hybrid_override_pattern=pattern))


# --- operation and byte counts, by hand --------------------------------------

def test_scan_operations_and_bytes_by_hand():
    """One sequence of 64 tokens, 8 heads of 8 on 2 groups' B and C of 16, at
    the configuration's chunk of 128: a position of a group the scores
    against its chunk, a position of a head their product with x, the
    chunk's state and its read-out; 2 operations a multiply-add, forward
    once and backward twice."""
    config = _tiny_config()
    a_position = 2 * (2 * 128 * 16) + 8 * (2 * 128 * 8 + 2 * 16 * 8 + 2 * 16 * 8)
    assert nemotron_h.ssm_core_flops_per_sample(config) == 3 * a_position * 64
    # x, y, dy, dx at the heads' width and v's... five arrays of 64 features,
    # six of a group's 16 a group, three float32 numbers a head
    assert nemotron_h.ssm_core_bytes_per_sample(config) == 64 * (
        5 * 64 * 2 + 6 * 2 * 16 * 2 + 3 * 8 * 4)
    real = _real()
    # the issue's arithmetic: 0.33 T for four scans, 0.084 T and 0.442 GB each
    assert nemotron_h.ssm_core_flops_per_sample(real) == pytest.approx(83.75e9, rel=1e-3)
    assert 4 * nemotron_h.ssm_core_flops_per_sample(real) == pytest.approx(0.33e12, rel=2e-2)
    assert nemotron_h.ssm_core_bytes_per_sample(real) == 8192 * (
        5 * 4096 * 2 + 6 * 1024 * 2 + 3 * 64 * 4) == 442_499_072
    # on the v5e the bytes bound the scan: 0.54 ms against 0.43 a layer
    peaks = harness.load_peaks("TPU v5 lite")
    t_flops = nemotron_h.ssm_core_flops_per_sample(real) / peaks["bf16_flops"]
    t_bytes = nemotron_h.ssm_core_bytes_per_sample(real) / peaks["hbm_bytes_per_s"]
    assert t_bytes == pytest.approx(0.540e-3, rel=1e-2) and t_flops < t_bytes
    assert t_flops == pytest.approx(0.425e-3, rel=1e-2)


def test_core_operations_and_bytes_by_hand():
    """One sequence of 64 tokens, 4 query heads on 2 key/value heads of 16:
    64 x 64 / 2 pairs a query head, 2 products forward and 4 backward over 16
    features, 2 operations a multiply-add."""
    config = _tiny_config()
    pairs = 64 * 64 / 2
    assert nemotron_h.attn_core_flops_per_sample(config) == 6 * 2 * pairs * 4 * 16
    # q, o, do, dq (and q, o read again) at 4 heads; k, v, dk, dv (k, v again) at 2
    assert nemotron_h.attn_core_bytes_per_sample(config) == 6 * (4 + 2) * 64 * 16 * 2
    real = _real()
    # the issue's arithmetic: 1.65 T for the one core
    assert nemotron_h.attn_core_flops_per_sample(real) == (
        6 * 2 * 8192 * 8192 / 2 * 32 * 128) == pytest.approx(1.649e12, rel=1e-3)
    assert nemotron_h.attn_core_bytes_per_sample(real) == 6 * 34 * 8192 * 128 * 2
    peaks = harness.load_peaks("TPU v5 lite")
    assert nemotron_h.attn_core_flops_per_sample(real) / peaks["bf16_flops"] == (
        pytest.approx(8.37e-3, rel=1e-2))


def test_flops_per_sample_by_hand():
    """Per token: a Mamba-2 layer (W_in 64 x (64 + 128 + 8), 4 taps over 128
    channels, W_out 64 x 64) twice; the attention layer (W_q and W_o 64 x 64,
    W_k and W_v 64 x 32); in the two expert layers the router over 16, the
    shared expert 2 x 64 x 64 and 3 x 4 / 16 of a routed expert of 2 x 64 x
    32; the head 320 x 64; 2 operations a multiply-add, x 3 for forward and
    backward, but x 2 for a router that is not trained, as the cell's are;
    the scans and the core."""
    config = _tiny_config()
    mamba = 64 * (64 + 128 + 8) + 4 * 128 + 64 * 64
    attention = 2 * 64 * 64 + 2 * 64 * 32
    experts = 64 * 16 + 2 * 64 * 64 + 0.75 * 2 * 64 * 32
    assert nemotron_h.layer_params_per_token(config, "M") == mamba
    assert nemotron_h.layer_params_per_token(config, "*") == attention
    assert nemotron_h.layer_params_per_token(config, "E") == experts
    params = 320 * 64 + 2 * mamba + attention + 2 * experts
    assert nemotron_h.expected_expert_passes(config) == 0.75
    assert nemotron_h.matmul_params_per_token(config) == params
    cores = (2 * nemotron_h.ssm_core_flops_per_sample(config)
             + nemotron_h.attn_core_flops_per_sample(config))
    assert config["routers_trained"] is False
    assert nemotron_h.flops_per_sample(config) == (
        3 * 2 * params * 64 - 2 * 2 * 64 * 16 * 64 + cores)
    assert nemotron_h.flops_per_sample({**config, "routers_trained": True}) == (
        3 * 2 * params * 64 + cores)
    real = _real()
    assert nemotron_h.expected_expert_passes(real) == 0.375
    # the issue's arithmetic: 4 x 38.71 + 23.40 + 4 x 24.04 + 44.04 M
    # multiplying parameters a token, 17.6 T a step
    assert nemotron_h.layer_params_per_token(real, "M") == pytest.approx(38.71e6, rel=1e-3)
    assert nemotron_h.layer_params_per_token(real, "*") == 23_396_352
    assert nemotron_h.layer_params_per_token(real, "E") == pytest.approx(24.04e6, rel=1e-3)
    assert nemotron_h.matmul_params_per_token(real) == pytest.approx(318.4e6, rel=1e-3)
    assert nemotron_h.flops_per_sample(real) == pytest.approx(17.6e12, rel=2e-3)
    shares = {kind: 4 * 6 * 8192 * nemotron_h.layer_params_per_token(real, kind)
              / nemotron_h.flops_per_sample(real) for kind in "ME"}
    # the new mechanisms do most of the work: 43 % and 27 % of the operations
    # in the Mamba-2 mixers' and the expert layers' matrices
    assert shares["M"] == pytest.approx(0.432, abs=5e-3)
    assert shares["E"] == pytest.approx(0.268, abs=5e-3)


def test_the_multiplying_parameters_are_the_initialised_trees():
    """Every matrix of the initialised tree multiplies every token once, but
    the embedding (a lookup) and the held experts (a token takes 6 x 8 / 128
    of one on average): the family's count from the configuration against
    the tree's own leaves."""
    real = _real()
    state = jax.eval_shape(lambda: nemotron_h.init(real, 0))
    # every leaf but the norms' scales, the biases and the numbers a head is
    # a matrix (the taps are one, of 4 rows)
    matrices = sum(
        x.size for path, x in jax.tree_util.tree_leaves_with_path(state)
        if not jax.tree_util.keystr(path).rstrip("']").endswith(
            ("_scale", "router_bias", "conv_b", "dt_bias", "A_log", "D_skip")))
    held = 4 * 8 * 2 * 2688 * 1856
    want = matrices - state["embed"].size - held + 4 * 0.375 * 2 * 2688 * 1856
    assert nemotron_h.matmul_params_per_token(real) == want


def test_host_batches_come_from_the_seed_uniform_over_the_slice():
    config = _tiny_config(sequence_length=4096)
    a = nemotron_h.host_batch(config, 2**31 + 11, 3, 2)
    b = nemotron_h.host_batch(config, 2**31 + 11, 3, 2)
    c = nemotron_h.host_batch(config, 2**31 + 12, 3, 2)
    assert a.shape == (2, 4097) and a.dtype == np.int32  # S + 1 ids
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < 320
    counts = np.bincount(a.ravel(), minlength=320)
    assert counts.min() > 5 and counts.max() < 64 and 150 < np.median(a) < 170
    real = nemotron_h.host_batch(_real(), 2**31 + 11, 0, 1)
    assert real.shape == (1, 8193) and real.max() < 16384
    assert np.bincount(real.ravel(), minlength=16384).max() < 10


# --- the program against the reference --------------------------------------

def _both(dtype, seed=5):
    config = _tiny_config(compute_dtype=dtype)
    state = nemotron_h.init(config, seed)
    sample = nemotron_h.host_batch(config, seed, 0, 2)
    got = nemotron_h.program_loss_and_grads(config)(state, sample)
    want = nemotron_h.reference_loss_and_grads(config, state, sample)
    return config, state, sample, got, want


def test_reference_equals_program_in_float32():
    config, state, sample, (loss, grads), (ref_loss, ref_grads) = _both("float32")
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    assert harness.relative_error(grads, ref_grads) <= 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    assert nemotron_h.differing_choices(config, state, sample) == 0
    stats = nemotron_h.routing_stats(config, state, sample)
    assert stats["dropped"] == [0, 0] and stats["layer"] == [1, 4]
    assert stats["held_rows"] == np.sum(stats["counts"], axis=1).tolist()
    assert len(stats["bias_moved"]) == 2
    # the routers' and the biases' gradients are zero in both (the cell)
    for tree in (grads, ref_grads):
        for at in (1, 4):
            assert not np.asarray(tree["layers"][at]["router"]).any()
            assert not np.asarray(tree["layers"][at]["router_bias"]).any()


def test_bfloat16_program_is_within_the_familys_tolerances():
    _, _, _, (loss, grads), (ref_loss, ref_grads) = _both("bfloat16")
    assert abs(float(loss) - float(ref_loss)) <= (
        nemotron_h.LOSS_RTOL * abs(float(ref_loss)))
    error = harness.relative_error(grads, ref_grads)
    assert 1e-4 < error <= nemotron_h.GRAD_RTOL, error
    assert 0 < nemotron_h.LOSS_RTOL < nemotron_h.GRAD_RTOL < 0.1


def test_the_reference_computes_in_blocks_what_it_computes_at_once():
    config = _tiny_config(compute_dtype="float32")
    state = nemotron_h.init(config, 3)
    sample = nemotron_h.host_batch(config, 3, 0, 1)
    from benchmark.reference import nemotron_h as reference

    whole = dict(nemotron_h._hyper(config), query_block=64, position_block=64)
    at_once = reference.loss_and_grads(state, sample, **whole)
    in_blocks = reference.loss_and_grads(
        state, sample, **{**whole, "query_block": 16, "position_block": 8})
    assert float(at_once[0]) == pytest.approx(float(in_blocks[0]), rel=1e-6)
    assert harness.relative_error(in_blocks[1], at_once[1]) <= 1e-5


def test_the_reference_imports_nothing_of_the_program():
    import benchmark.reference.nemotron_h as reference

    with open(reference.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "kungfu_tpu" in line]
    assert "pallas" not in text and "custom_vjp" not in text
    assert 'default_matmul_precision("highest")' in text
    assert nemotron_h.REFERENCE_SAMPLES == 1


def test_the_cells_program_holds_to_its_declared_precision():
    config = _tiny_config()
    state = jax.eval_shape(lambda: nemotron_h.init(config, 0))
    sample = nemotron_h.host_batch(config, 0, 0, nemotron_h.REFERENCE_SAMPLES)
    traced = nemotron_h.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, nemotron_h.head_width(config),
                                    traced.jaxpr, state, state) == []
    low = _tiny_config(param_dtype="bfloat16")
    assert harness.precision_faults(low, nemotron_h.head_width(low),
                                    traced.jaxpr, state, state)


def test_the_real_program_holds_to_its_declared_precision():
    """At the published widths, from shapes alone: no matmul or reduction
    over the head's 16,384 rows is in bfloat16, and no other array of the
    step has that width."""
    config = _real()
    state = jax.eval_shape(lambda: nemotron_h.init(config, 0))
    sample = nemotron_h.host_batch(config, 0, 0, nemotron_h.REFERENCE_SAMPLES)
    traced = nemotron_h.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, nemotron_h.head_width(config),
                                    traced.jaxpr, state, state) == []


# --- the whole of measure ----------------------------------------------------

@pytest.fixture(scope="module")
def events():
    return harness.EventCounter()


def test_measure_at_tiny_size_on_two_cpu_devices(events):
    """State, pool, first step, warm-up, probe, window, checks and the
    reference, on a dp = 2 mesh of virtual CPU devices."""
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
    assert record["flops_per_sample"] == nemotron_h.flops_per_sample(cell["config"])
    json.dumps(record)
    assert all(v > 0 for v in end_to_end.values(record).values())
    with pytest.raises(RuntimeError, match="chip runs only"):
        end_to_end.result_line(record, None, m)


# --- the readers on a drawn trace ---------------------------------------

MS = 8_000_000  # a unit of the drawing below, in ns: 8 ms
# Two steps of 60 units on one chip, each alike:
#   ssm.in [0, 3)  conv [3, 4)  scan.fwd [4, 6)  ssm.norm [6, 7)  ssm.out [7, 8)
#   qkv [8, 9)  core.fwd [9, 11)  wo [11, 12)  router [12, 13)  gmm.fwd [13, 15)
#   shared.fwd [15, 16)  head [16, 19)  head.bwd [19, 23)  shared.bwd [23, 25)
#   gmm.bwd [25, 29)  share.bwd [29, 30) (under `moe` alone)  core.bwd [30, 35)
#   qkv.bwd [35, 37)  ssm.out.bwd [37, 39)  ssm.norm.bwd [39, 40)
#   scan.bwd [40, 45)  dqk.sum [45, 45.5)  conv.bwd [45.5, 47)  ssm.in.bwd [47, 53)
#   adamw [53, 56) (under `optimizer`)
STEP_OPS = [("ssm.in", 0, 3), ("conv", 3, 4), ("scan.fwd", 4, 6),
            ("ssm.norm", 6, 7), ("ssm.out", 7, 8), ("qkv", 8, 9),
            ("core.fwd", 9, 11), ("wo", 11, 12), ("router", 12, 13),
            ("gmm.fwd", 13, 15), ("shared.fwd", 15, 16), ("head", 16, 19),
            ("head.bwd", 19, 23), ("shared.bwd", 23, 25), ("gmm.bwd", 25, 29),
            ("share.bwd", 29, 30), ("core.bwd", 30, 35), ("qkv.bwd", 35, 37),
            ("ssm.out.bwd", 37, 39), ("ssm.norm.bwd", 39, 40),
            ("scan.bwd", 40, 45), ("dqk.sum", 45, 45.5),
            ("conv.bwd", 45.5, 47), ("ssm.in.bwd", 47, 53), ("adamw", 53, 56)]
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
    "ssm.in": f"{FWD}/ssm/ssm_proj/dot_general",
    "conv": f"{FWD}/ssm/ssm_conv/mul",
    "scan.fwd": f"{FWD}/ssm/ssm_core/ssm_scan_forward/pallas_call",
    "ssm.norm": f"{FWD}/ssm/ssm_norm/rsqrt",
    "ssm.out": f"{FWD}/ssm/ssm_proj/dot_general",
    "qkv": f"{FWD}/attn/dot_general",
    "core.fwd": f"{FWD}/attn/attn_full/attn_core/pallas_call",
    "wo": f"{FWD}/attn/dot_general",
    "router": f"{FWD}/moe/moe_router/dot_general",
    "gmm.fwd": "ragged-dot-none",
    "shared.fwd": f"{FWD}/moe/moe_shared/dot_general",
    "head": "jit(step)/shard_map/jvp(head_loss)/dot_general",
    "head.bwd": "jit(step)/shard_map/transpose(jvp(head_loss))/dot_general",
    "shared.bwd": f"{BWD}/moe/moe_shared/dot_general",
    "gmm.bwd": "ragged-dot-none",
    "share.bwd": f"{BWD}/moe/gather",
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


def _record(samples_per_step=1):
    return {"workload": CELL, "scopes": SCOPES, "samples_per_step": samples_per_step,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_drawn_times():
    record = _record()
    # both kernels and the sum of a group's dq and dk after the second
    assert ssm_core_ms.read(record, DRAWN) == pytest.approx(8 * (2 + 5 + 0.5))
    # `ssm` less the scan: projections, convolution, the gated norm
    assert ssm_mix_ms.read(record, DRAWN) == pytest.approx(
        8 * (3 + 1 + 1 + 1 + 2 + 1 + 1.5 + 6))
    assert nope_core_ms.read(record, DRAWN) == pytest.approx(8 * (2 + 5))
    # `moe` with the kernels claimed by name, the share's own backward among it
    assert moe_relu2_ms.read(record, DRAWN) == pytest.approx(
        8 * (1 + 2 + 1 + 2 + 4 + 1))


def test_drawn_shares_of_the_rooflines():
    """At the real widths: one sequence of 8,192 tokens a step, four scans
    bound by their bytes and one core bound by its operations."""
    record = _record()
    peaks = harness.load_peaks("TPU v5 lite")
    scans = 4 * 442_499_072 / peaks["hbm_bytes_per_s"]
    assert ssm_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * scans / 60e-3)
    core = 6 * 2 * (8192 * 8192 / 2) * 32 * 128 / peaks["bf16_flops"]
    assert nope_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * core / 56e-3)
    # a step of two sequences has twice the work in the same drawn time
    for reader in (ssm_core_roofline_pct, nope_core_roofline_pct):
        assert reader.read(_record(2), DRAWN) == pytest.approx(
            2 * reader.read(record, DRAWN))
    assert 3 < ssm_core_roofline_pct.read(record, DRAWN) < 4
    assert 14 < nope_core_roofline_pct.read(record, DRAWN) < 16


def test_the_rooflines_count_nothing_a_core_might_skip():
    """The causal half, each of the six products once; the chunked form's
    four products forward once and backward twice; every array once each
    way: no recomputation, and not the chunk states the scan keeps."""
    real = _real()
    assert nemotron_h.attn_core_flops_per_sample(real) == (
        6 * 2 * 8192 * 8192 / 2 * 32 * 128)
    assert nemotron_h.ssm_core_flops_per_sample(real) == 3 * 8192 * (
        8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 128 * 64))
    kept = 64 * (8192 // 128) * 128 * 64 * 4  # the states the scan keeps
    assert kept == 134_217_728
    assert nemotron_h.ssm_core_bytes_per_sample(real) < 442_499_072 + kept


READERS = (ssm_core_ms, ssm_core_roofline_pct, ssm_mix_ms, nope_core_ms,
           nope_core_roofline_pct, moe_relu2_ms)


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__.split(".")[-1])
def test_readers_find_nothing_without_a_trace_or_a_scope_table(reader):
    assert reader.read(_record(), None) is None
    assert reader.read(_record(), {"chips": [], "host": [], "lines": {}}) is None
    for scopes in (None, {}):
        assert reader.read({**_record(), "scopes": scopes}, DRAWN) is None
    assert reader.read({"workload": CELL}, DRAWN) is None


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__.split(".")[-1])
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
    assert not {"full_core_ms", "moe_share_ms", "flash_roofline_pct",
                "flash_core_ms", "moe_ms", "gdn_core_ms", "gdn_mix_ms",
                "gattn_core_ms", "moe_held_ms", "moe_sigmoid_ms",
                "mla_core_ms"} & mine
    assert line["metrics"]["optimizer_ms"]["value"] == pytest.approx(8 * 3.0)
    assert line["metrics"]["head_loss_ms"]["value"] == pytest.approx(8 * (3 + 4))
    assert line["metrics"]["ssm_core_ms"]["value"] == pytest.approx(8 * 7.5)
    assert line["metrics"]["ssm_core_roofline_pct"]["unit"] == "%"
    assert mf.check_result_line(line, manifest, CELL, traced=True) == []
