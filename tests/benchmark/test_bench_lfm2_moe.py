"""The LFM2-MoE family, its configuration and its four readers (PR 57): the
whole of `harness.measure` at tiny size on the CPU mesh, the parameter,
operation and byte counts against the initialised tree and sums made by hand,
the batches, the readers against a drawn trace, and the configuration file
against the catalog's numbers.

The cell's slice of the vocabulary is as wide as its sequence is long (ISSUE
57: 8,192 rows, 8,192 positions), and `harness.precision_faults` finds the
head's products by a dimension of the head's width: program and reference are
compared on the first `reference_positions` (4,096) positions of the harness's
sample (`families.lfm2_moe.compared`, PERF.md section 7); the timed step runs
all 8,192."""

import copy
import json
import time

import jax
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.families import lfm2_moe
from benchmark.launchers.none import OneProcess
from benchmark.layer_metrics import (sconv_attn_core_roofline_pct,
                                     sconv_core_ms, sconv_core_roofline_pct,
                                     sconv_mix_ms)
from drawn_setup import child_marks, drawn_setup

CELL = "lfm2_24b_a2b.ssgd_conv_8k_1chip"
NAME = "lfm2_24b_a2b"
MINE = (("sconv_core_ms", "ms", "lower", "Kernels"),
        ("sconv_core_roofline_pct", "%", "higher", "Kernels"),
        ("sconv_mix_ms", "ms", "lower", "Model"),
        ("sconv_attn_core_roofline_pct", "%", "higher", "Kernels"))
# accepted readers of scopes this cell's program has, whose lists it joins:
# `moe` with the grouped-matmul kernels, `moe_experts`, the router, dispatch
# and combine, `attn_full`, `attn` less the cores, `ffn`
JOINED = ("optimizer_ms", "head_loss_ms", "moe_ms", "expert_ffn_ms",
          "moe_dispatch_ms", "full_core_ms", "attn_proj_ms", "pk_ffn_ms")
CONFIG = {
    "name": NAME,
    "source": "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json",
    "file": "benchmark/configs/lfm2_24b_a2b.json",
    "reduced": ["num_hidden_layers", "num_experts", "vocab_size"]}
PER_LAYER = [
    {"name": name, "unit": unit, "better": better, "source": "device_trace",
     "layer": layer, "moves": "step_ms_p50", "workloads": [CELL]}
    for name, unit, better, layer in MINE]


# every mechanism on, at the tests' size (tests/family_cases.py): `c c a c`,
# 128 channels so that the convolution's kernels run (interpreted)
TINY = dict(hidden_size=128, intermediate_size=192, moe_intermediate_size=32,
            num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
            num_experts=8, first_expert_held=4, published={"num_experts": 16},
            vocab_size=320, sequence_length=64, flash_blocks=[32, 32],
            flash_interpret=True)  # 320: no layer's width

# LiquidAI/LFM2-24B-A2B's config.json as the catalog has it
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv", "full_attention", "conv"] * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def _real():
    return mf.cell(mf.load(), CELL)["config"]


def _tiny_config(**changes):
    config = copy.deepcopy(_real())
    config.update(copy.deepcopy(TINY))
    config.update(changes)
    return config


def test_the_manifest_with_the_eleventh_cell_is_sound():
    manifest = mf.load()
    assert mf.check(manifest) == []
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": NAME, "traffic": "ssgd_conv_8k_1chip",
                    "chips": 1}
    for word in ("8,192", "one document", "6 conv", "4,096 of 32,768", "1/8"):
        assert word in cell["why"], word
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry == {**CONFIG, "why": entry["why"]}
    for word in ("gated short conv", "3 conv : 1", "top-4-of-64", "share of 8",
                 "1/8 vocabulary"):
        assert word in entry["why"], word
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == PER_LAYER
    assert sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", []) and m not in mine) == sorted(JOINED)
    assert len(manifest["configs"]) >= 10 and len(manifest["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # additions at the ends: the tenth cell's entries stand right before these
    at = [w["name"] for w in manifest["workloads"]].index(CELL)
    assert manifest["workloads"][at - 1]["name"] == "granite_4_0_h_micro.ssgd_packed_1chip"
    assert manifest["configs"][at - 2]["name"] == "granite_4_0_h_micro"
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[names.index("sconv_core_ms") - 1] == "pk_within_doc_pairs_pct"
    for metric in manifest["per_layer"]:
        if metric["name"] in JOINED:
            assert metric["workloads"].index(CELL) >= 1  # behind what was there


def test_the_configuration_is_the_catalogs_but_for_its_cut():
    manifest = mf.load()
    config = _real()
    differs = sorted(k for k, v in CATALOG.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (8, 8, 8192)
    assert config["published"] == {k: CATALOG[k] for k in config["reduced"]}
    # one chip's eighth of an 8-chip layer, of the experts and of the rows
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["num_experts"] * 8 == CATALOG["num_experts"]
    # both dense layers and two whole periods `a c c c`: 6 to 2, the published 3 : 1
    assert [m[0] for m, _ in lfm2_moe.layer_types(config)] == list("ccfcccfc")
    assert [f for _, f in lfm2_moe.layer_types(config)] == ["dense"] * 2 + ["sparse"] * 6
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["source"] == config["source"] and entry["source"].endswith(
        "LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert "8 chips" in config["deployment"] and len(config["assumed"]) >= 12
    assert "8,192 of 65,536 rows a chip" in config["deployment"]
    for word in ("tied", "B | C | x", "rotate-half", "epsilon", "normal(0, 0.01)",
                 "normal(0, 0.02)", "uniform", "recomputed", "routers_trained",
                 "3e-4", "layers 0 to 5", "reference_positions 4096"):
        assert any(word in line for line in config["assumed"]), word
    assert config["sequence_length"] == 8192 and config["tie_word_embeddings"]
    assert config["routers_trained"] is False
    traffic = mf.cell(manifest, CELL)["traffic"]
    assert (traffic["per_chip_batch"], traffic["pool"], traffic["mesh"]) == (
        1, 8, {"dp": 1})
    assert (traffic["launcher"], traffic["step"], traffic["placement"]) == (
        "none", "ssgd", "shard_batch")
    assert traffic["optimizer"] == {"name": "adamw", "learning_rate": 0.0003}


def test_the_cut_holds_the_parameters_its_file_says():
    """ISSUE 57's count, by `eval_shape`, against the file's `parameters` and
    `state_bytes`: 16.78 M a convolution mixer, 10.49 M an attention mixer,
    72.35 M a dense feed-forward, 75.50 M the experts held of a layer."""
    config = _real()
    state = jax.eval_shape(lambda: lfm2_moe.init(config, 0))

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    dense, attention, conv = state["layers"][:3]
    assert size({k: dense[k] for k in ("conv_in", "conv_w", "conv_out")}) / 2 == (
        16_783_360) == 4 * 2048 * 2048 + 3 * 2048
    assert size({k: attention[k] for k in ("wq", "wk", "wv", "wo", "q_norm_scale",
                                           "k_norm_scale")}) == 10_485_888
    assert size({k: dense[k] for k in ("w_gate", "w_up", "w_down")}) / 2 == 72_351_744
    assert size(dense) / 2 == 89_139_200
    assert size(attention) == 86_118_592 and size(conv) / 3 == 92_416_064
    assert conv["router"].shape == (3, 2048, 64) and conv["router_bias"].shape == (3, 64)
    assert size({k: conv[k] for k in ("w_gate", "w_up", "w_down")}) / 3 == 75_497_472
    assert size(state["embed"]) == 8192 * 2048 and "lm_head" not in state
    assert size(state) == config["parameters"] == 736_959_104
    assert 16 * size(state) == config["state_bytes"] == 11_791_345_664
    assert 0.69 < config["state_bytes"] / 16.9e9 < 0.71  # 70 % of the chip
    # the floor the issue names, layers 0 to 5
    floor = jax.eval_shape(lambda: lfm2_moe.init({**config, "num_hidden_layers": 6}, 0))
    assert size(floor) == 558_424_448
    mc = lfm2_moe.model_config(config)
    assert mc.experts_held == (0, 8) and mc.n_experts == 64 and mc.top_k == 4
    assert [(k.mixer, k.ffn, k.layer_remat, n) for k, n in mc.stacks] == [
        ("short_conv", "swiglu", False, 2), ("attention", "moe", True, 1),
        ("short_conv", "moe", True, 3), ("attention", "moe", True, 1),
        ("short_conv", "moe", True, 1)]


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("norm_topk_prob", False), ("use_expert_bias", False),
    ("tie_word_embeddings", False), ("num_dense_layers", 0),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"})])
def test_the_family_refuses_a_layer_it_does_not_run(key, value):
    with pytest.raises(ValueError, match="as published"):
        lfm2_moe.model_config(_tiny_config(**{key: value}))
    with pytest.raises(ValueError, match="do not name"):
        lfm2_moe.layer_types(_tiny_config(layer_types=["conv", "mamba"] * 2))


# --- operation and byte counts, by hand --------------------------------------

def test_core_operations_and_bytes_by_hand():
    real = _real()
    # the issue's roof: 100.7 + 33.6 MB forward, 100.7 + 33.6 + 100.7 backward
    assert lfm2_moe.conv_core_bytes_per_sample(real) == (4 + 7) * 8192 * 2048 * 2
    assert lfm2_moe.conv_core_flops_per_sample(real) == 3 * 8 * 2048 * 8192
    peaks = harness.load_peaks("TPU v5 lite")
    forward = 4 * 8192 * 2048 * 2 / peaks["hbm_bytes_per_s"]
    backward = 7 * 8192 * 2048 * 2 / peaks["hbm_bytes_per_s"]
    assert (forward * 1e3, backward * 1e3) == (
        pytest.approx(0.164, abs=1e-3), pytest.approx(0.287, abs=1e-3))
    # a causal core: 32 query heads of 64 over S^2 / 2 pairs, six products
    assert lfm2_moe.attn_core_flops_per_sample(real) == (
        6 * 2 * 8192 * 8192 / 2 * 32 * 64) == pytest.approx(0.8246e12, rel=1e-4)
    assert lfm2_moe.attn_core_bytes_per_sample(real) == 6 * (32 + 8) * 64 * 8192 * 2
    tiny = _tiny_config()
    assert lfm2_moe.attn_core_flops_per_sample(tiny) == 6 * 2 * 2048 * 4 * 32
    assert lfm2_moe.conv_core_bytes_per_sample(tiny) == 11 * 128 * 64 * 2


def test_flops_per_sample_by_hand():
    """Per token at the tests' size: three convolution mixers (W_in 128 x 384,
    3 taps a channel, W_out 128 x 128) and one attention mixer (W_q and W_o
    128 x 128, W_k and W_v 128 x 64); two dense feed-forwards 3 x 128 x 192;
    in the two expert layers the router over 16 and 4 x 8 / 16 of a routed
    expert; the tied head 320 x 128 once; 2 operations a multiply-add, x 3 for
    forward and backward, but x 2 for a router that is not trained; one core."""
    config = _tiny_config()
    conv = 128 * 384 + 3 * 128 + 128 * 128
    attention = 2 * 128 * 128 + 2 * 128 * 64
    assert lfm2_moe.mixer_params_per_token(config, "conv") == conv
    assert lfm2_moe.mixer_params_per_token(config, "full_attention") == attention
    expert = 3 * 128 * 32
    assert lfm2_moe.expected_expert_passes(config) == 2.0
    params = (320 * 128 + 3 * conv + attention + 2 * 3 * 128 * 192
              + 2 * (128 * 16 + 2.0 * expert))
    assert lfm2_moe.matmul_params_per_token(config) == params
    core = 6 * 2 * 2048 * 4 * 32
    assert config["routers_trained"] is False
    assert lfm2_moe.flops_per_sample(config) == (
        3 * 2 * params * 64 - 2 * 2 * 128 * 16 * 64 + core)
    assert lfm2_moe.flops_per_sample({**config, "routers_trained": True}) == (
        3 * 2 * params * 64 + core)
    real = _real()
    assert lfm2_moe.expected_expert_passes(real) == 0.5
    # the issue's arithmetic: 86 ms of required work at the peak
    assert lfm2_moe.matmul_params_per_token(real) == pytest.approx(312.3e6, rel=1e-3)
    assert lfm2_moe.flops_per_sample(real) / 197e12 == pytest.approx(0.0862, rel=1e-2)


def test_the_multiplying_parameters_are_the_initialised_trees():
    """Every matrix of the initialised tree multiplies every token once, but
    the held experts (a token takes 4 x 8 / 64 of one on average): the
    family's count from the configuration against the tree's own leaves (the
    tied embedding once, as the head)."""
    real = _real()
    state = jax.eval_shape(lambda: lfm2_moe.init(real, 0))
    matrices = sum(
        x.size for path, x in jax.tree_util.tree_leaves_with_path(state)
        if not jax.tree_util.keystr(path).rstrip("']").endswith(
            ("_scale", "router_bias")))
    experts = 6 * 8 * 3 * 2048 * 1536
    assert lfm2_moe.matmul_params_per_token(real) == (
        matrices - experts + 6 * 0.5 * 3 * 2048 * 1536)


def test_host_batches_come_from_the_seed_uniform_over_the_slice():
    config = _tiny_config(sequence_length=4096)
    a = lfm2_moe.host_batch(config, 2**31 + 11, 3, 2)
    b = lfm2_moe.host_batch(config, 2**31 + 11, 3, 2)
    c = lfm2_moe.host_batch(config, 2**31 + 12, 3, 2)
    assert a.shape == (2, 4097) and a.dtype == np.int32  # S + 1 ids
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < 320
    counts = np.bincount(a.ravel(), minlength=320)
    assert counts.min() > 5 and counts.max() < 64 and 150 < np.median(a) < 170
    real = lfm2_moe.host_batch(_real(), 2**31 + 11, 0, 1)
    assert real.shape == (1, 8193) and 8100 < real.max() < 8192


# --- the program against the reference --------------------------------------

def test_the_reference_computes_in_blocks_what_it_computes_at_once():
    config = _tiny_config(compute_dtype="float32")
    state = lfm2_moe.init(config, 3)
    sample = lfm2_moe.host_batch(config, 3, 0, 1)
    from benchmark.reference import lfm2_moe as reference

    whole = dict(lfm2_moe._hyper(config), query_block=64, position_block=64)
    at_once = reference.loss_and_grads(state, sample, **whole)
    in_blocks = reference.loss_and_grads(
        state, sample, **{**whole, "query_block": 16, "position_block": 16})
    assert float(at_once[0]) == pytest.approx(float(in_blocks[0]), rel=1e-6)
    assert harness.relative_error(in_blocks[1], at_once[1]) <= 1e-5


def test_the_reference_imports_nothing_of_the_program_or_of_another_reference():
    import benchmark.reference.lfm2_moe as reference

    with open(reference.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports
                            if "kungfu_tpu" in line or "benchmark" in line]
    assert "pallas" not in text and "custom_vjp" not in text
    assert 'default_matmul_precision("highest")' in text
    assert lfm2_moe.REFERENCE_SAMPLES == 1


def test_the_comparison_reads_the_samples_first_positions(monkeypatch):
    """`compared` cuts the harness's sample for program and reference alike;
    a configuration without the key is compared on all of it."""
    from benchmark.reference import lfm2_moe as reference

    config = _tiny_config(compute_dtype="float32", reference_positions=32,
                          num_hidden_layers=2, num_dense_layers=1)
    whole = {k: v for k, v in config.items() if k != "reference_positions"}
    state = lfm2_moe.init(config, 5)
    sample = lfm2_moe.host_batch(config, 5, 0, 1)
    assert lfm2_moe.compared(config, sample).shape == (1, 33)
    assert lfm2_moe.compared(whole, sample).shape == (1, 65)
    loss, grads = lfm2_moe.program_loss_and_grads(config)(state, sample)
    first, first_grads = lfm2_moe.program_loss_and_grads(whole)(state, sample[:, :33])
    assert float(loss) == float(first)
    assert harness.relative_error(grads, first_grads) == 0.0
    seen = []
    monkeypatch.setattr(reference, "loss_and_grads", lambda state, batch, **hyper: (
        seen.append(batch.shape), (0.0, state))[1])
    lfm2_moe.reference_loss_and_grads(config, state, sample)
    lfm2_moe.reference_loss_and_grads(whole, state, sample)
    assert seen == [(1, 33), (1, 65)]


def test_the_real_program_holds_to_its_declared_precision():
    """At ISSUE 57's sizes, traced and not run. The head is 8,192 rows wide
    and the sequence 8,192 positions long: over the whole sample
    `harness.precision_faults` refuses the mixers' and feed-forwards' bfloat16
    products as the head's; over the first 4,096 positions, which is what the
    cell compares, the head's products alone have 8,192 in a shape, and a
    bfloat16 head is caught there."""
    config = _real()
    assert lfm2_moe.head_width(config) == config["sequence_length"] == 8192
    assert config["reference_positions"] == 4096
    state = jax.eval_shape(lambda: lfm2_moe.init(config, 0))
    sample = lfm2_moe.host_batch(config, 0, 0, lfm2_moe.REFERENCE_SAMPLES)
    traced = lfm2_moe.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, 8192, traced.jaxpr, state, state) == []
    whole = {k: v for k, v in config.items() if k != "reference_positions"}
    traced = lfm2_moe.program_loss_and_grads(whole).trace(state, sample)
    faults = harness.precision_faults(whole, 8192, traced.jaxpr, state, state)
    assert faults and all("bfloat16" in fault for fault in faults)
    assert any("(1, 8192, 2048), (2048, 6144)" in fault for fault in faults)  # W_in
    low = {**config, "head_dtype": "bfloat16"}
    traced = lfm2_moe.program_loss_and_grads(low).trace(state, sample)
    faults = harness.precision_faults(low, 8192, traced.jaxpr, state, state)
    assert faults and all("float32" in fault for fault in faults)
    assert any("(1, 4096, 2048)" in fault and "8192" in fault for fault in faults)


def test_the_cells_program_holds_to_its_declared_precision():
    config = _tiny_config()
    state = jax.eval_shape(lambda: lfm2_moe.init(config, 0))
    sample = lfm2_moe.host_batch(config, 0, 0, lfm2_moe.REFERENCE_SAMPLES)
    traced = lfm2_moe.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, lfm2_moe.head_width(config),
                                    traced.jaxpr, state, state) == []
    low = _tiny_config(param_dtype="bfloat16")
    assert harness.precision_faults(low, lfm2_moe.head_width(low),
                                    traced.jaxpr, state, state)


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
    assert record["flops_per_sample"] == lfm2_moe.flops_per_sample(cell["config"])
    json.dumps(record)
    assert all(v > 0 for v in end_to_end.values(record).values())
    with pytest.raises(RuntimeError, match="chip runs only"):
        end_to_end.result_line(record, None, m)


# --- the readers on a drawn trace ---------------------------------------

MS = 8_000_000  # a unit of the drawing below, in ns: 8 ms
# Two steps of 60 units on one chip, each alike:
#   embed [0, 0.5)  conv_in [0.5, 2.5)  conv.fwd [2.5, 3)  conv_out [3, 4)
#   ffn.fwd [4, 8)  qkv [8, 9)  rope [9, 9.5)  core.fwd [9.5, 11.5)  wo [11.5, 12)
#   router [12, 12.5)  gmm.fwd [12.5, 14.5)  head [14.5, 17)  head.bwd [17, 20)
#   gmm.bwd [20, 24)  conv.again [24, 24.5)  core.bwd [24.5, 29.5)  qkv.bwd [29.5, 31)
#   conv_out.bwd [31, 33)  conv.bwd [33, 34)  taps.sum [34, 34.25)
#   conv_in.bwd [34.25, 38.25)  ffn.bwd [38.25, 46.25)  adamw [46.25, 49.25)
#   (under `optimizer`)  stray [49.25, 49.75) (no scope)
STEP_OPS = [("embed", 0, 0.5), ("conv_in", 0.5, 2.5), ("conv.fwd", 2.5, 3),
            ("conv_out", 3, 4), ("ffn.fwd", 4, 8), ("qkv", 8, 9), ("rope", 9, 9.5),
            ("core.fwd", 9.5, 11.5), ("wo", 11.5, 12), ("router", 12, 12.5),
            ("gmm.fwd", 12.5, 14.5), ("head", 14.5, 17), ("head.bwd", 17, 20),
            ("gmm.bwd", 20, 24), ("conv.again", 24, 24.5),
            ("core.bwd", 24.5, 29.5), ("qkv.bwd", 29.5, 31),
            ("conv_out.bwd", 31, 33), ("conv.bwd", 33, 34), ("taps.sum", 34, 34.25),
            ("conv_in.bwd", 34.25, 38.25), ("ffn.bwd", 38.25, 46.25),
            ("adamw", 46.25, 49.25), ("stray", 49.25, 49.75)]
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
    "embed": "jit(step)/shard_map/jvp(embed)/gather",
    "conv_in": f"{FWD}/sconv/sconv_proj/dot_general",
    "conv.fwd": f"{FWD}/sconv/sconv_core/short_conv_forward/pallas_call",
    "conv_out": f"{FWD}/sconv/sconv_proj/dot_general",
    "ffn.fwd": f"{FWD}/ffn/dot_general",
    "qkv": f"{FWD}/attn/dot_general",
    "rope": f"{FWD}/attn/rope/pallas_call",
    "core.fwd": f"{FWD}/attn/attn_full/attn_core/pallas_call",
    "wo": f"{FWD}/attn/dot_general",
    "router": f"{FWD}/moe/moe_router/dot_general",
    "gmm.fwd": "ragged-dot-none",
    "head": "jit(step)/shard_map/jvp(head_loss)/dot_general",
    "head.bwd": "jit(step)/shard_map/transpose(jvp(head_loss))/dot_general",
    "gmm.bwd": "ragged-dot-none",
    "conv.again": f"{BWD}/sconv/sconv_core/short_conv_forward/pallas_call",
    "core.bwd": f"{BWD}/attn/attn_full/attn_core/pallas_call",
    "qkv.bwd": f"{BWD}/attn/dot_general",
    "conv_out.bwd": f"{BWD}/sconv/sconv_proj/dot_general",
    "conv.bwd": f"{BWD}/sconv/sconv_core/short_conv_backward/pallas_call",
    "taps.sum": f"{BWD}/sconv/sconv_core/reduce_sum",
    "conv_in.bwd": f"{BWD}/sconv/sconv_proj/dot_general",
    "ffn.bwd": f"{BWD}/ffn/dot_general",
    "adamw": "jit(step)/shard_map/optimizer/optimizer_update/add",
}


def _record(samples_per_step=1):
    return {"workload": CELL, "scopes": SCOPES, "samples_per_step": samples_per_step,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_drawn_times():
    record = _record()
    # the operator: both kernels, the forward run again, the taps' sums
    assert sconv_core_ms.read(record, DRAWN) == pytest.approx(8 * (0.5 + 0.5 + 1 + 0.25))
    # `sconv` less the operator: both projections, forward and backward
    assert sconv_mix_ms.read(record, DRAWN) == pytest.approx(8 * (2 + 1 + 2 + 4))


def test_drawn_shares_of_the_rooflines():
    """At the real widths: one sequence of 8,192 tokens a step, six
    convolution layers bound by their bytes and two cores by their
    operations."""
    record = _record()
    peaks = harness.load_peaks("TPU v5 lite")
    conv = 6 * 11 * 8192 * 2048 * 2 / peaks["hbm_bytes_per_s"]
    assert conv > 6 * 3 * 8 * 2048 * 8192 / peaks["bf16_flops"]
    assert sconv_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * conv / 18e-3)
    cores = 2 * 6 * 2 * (8192 * 8192 / 2) * 32 * 64 / peaks["bf16_flops"]
    assert cores > 2 * 6 * 40 * 64 * 8192 * 2 / peaks["hbm_bytes_per_s"]
    assert sconv_attn_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * cores / 56e-3)
    # a step of two sequences has twice the work in the same drawn time
    for reader in (sconv_core_roofline_pct, sconv_attn_core_roofline_pct):
        assert reader.read(_record(2), DRAWN) == pytest.approx(
            2 * reader.read(record, DRAWN))
        assert 0 < reader.read(record, DRAWN) < 100
    # a record of another family's cell has no such layers to count
    other = {**record, "workload": "glm_4_7_flash.ssgd_mtp_8k_1chip"}
    assert sconv_core_roofline_pct.read(other, DRAWN) is None


READERS = (sconv_core_ms, sconv_core_roofline_pct, sconv_mix_ms,
           sconv_attn_core_roofline_pct)


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
              "program_memory": {"total_bytes": 17_510_000_000},
              "memory_stats_peak_bytes": 1, "correct": True, "attempted": 20,
              "failed": 0}
    line = end_to_end.result_line(record, DRAWN, manifest)
    mine = {x["name"] for x in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(line["metrics"]) == mine
    assert {r.__name__.split(".")[-1] for r in READERS} <= mine
    assert set(JOINED) <= mine
    assert not {"moe_sigmoid_ms", "pk_attn_core_ms", "gattn_core_ms",
                "flash_core_ms", "ssm_core_ms"} & mine
    value = lambda name: line["metrics"][name]["value"]
    assert value("optimizer_ms") == pytest.approx(8 * 3.0)
    assert value("head_loss_ms") == pytest.approx(8 * (2.5 + 3))
    # the accepted readers the cell joins, on this cell's scopes: `moe` with
    # the grouped-matmul kernels claimed by name; the kernels alone (the drawn
    # step has no op under `moe_experts`); the router; the two cores; `attn`
    # less them (projections, rotary pass); the dense layers
    assert value("moe_ms") == pytest.approx(8 * (0.5 + 2 + 4))
    assert value("expert_ffn_ms") == pytest.approx(8 * (2 + 4))
    assert value("moe_dispatch_ms") == pytest.approx(8 * 0.5)
    assert value("full_core_ms") == pytest.approx(8 * (2 + 5))
    assert value("attn_proj_ms") == pytest.approx(8 * (1 + 0.5 + 0.5 + 1.5))
    assert value("pk_ffn_ms") == pytest.approx(8 * (4 + 8))
    # the nine that claim device time leave the embedding and the stray op
    step = sum(b - a for _, a, b in STEP_OPS)
    claimed = sum(value(name) for name in (
        "sconv_core_ms", "sconv_mix_ms", "full_core_ms", "attn_proj_ms", "moe_ms",
        "pk_ffn_ms", "head_loss_ms", "optimizer_ms"))
    assert 8 * step - claimed == pytest.approx(8 * (0.5 + 0.5))
    assert line["metrics"]["sconv_core_roofline_pct"]["unit"] == "%"
    assert mf.check_result_line(line, manifest, CELL, traced=True) == []
