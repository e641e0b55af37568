"""The Xing4.0 family, its configuration and its six readers (PR 71): the
configuration file against the catalog's numbers, the parameter and operation
counts against the initialised tree and sums made by hand, the batches, the
declared precision of the program at the real sizes, and the readers, the new
six and the five the cell joined, against a drawn trace."""

import copy

import jax
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.families import xing4_0
from benchmark.layer_metrics import (mhc_maps_ms, mhc_ms, mhc_stream_roofline_pct,
                                     yarn_mla_core_ms, yarn_mla_core_roofline_pct,
                                     yarn_mla_proj_ms)
from drawn_setup import drawn_setup

CELL = "xing4_0_29b_a4b.ssgd_mhc_4k_1chip"
NAME = "xing4_0_29b_a4b"
MINE = (("mhc_ms", "ms", "lower", "Model"),
        ("mhc_maps_ms", "ms", "lower", "Model"),
        ("mhc_stream_roofline_pct", "%", "higher", "Kernels"),
        ("yarn_mla_core_ms", "ms", "lower", "Kernels"),
        ("yarn_mla_core_roofline_pct", "%", "higher", "Kernels"),
        ("yarn_mla_proj_ms", "ms", "lower", "Model"))
# accepted readers of scopes this cell's program has, whose lists it joins
JOINED = ("optimizer_ms", "head_loss_ms", "moe_ms", "expert_ffn_ms",
          "moe_dispatch_ms")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "num_nextn_predict_layers"]
CONFIG = {
    "name": NAME,
    "source": "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json",
    "file": "benchmark/configs/xing4_0_29b_a4b.json",
    "reduced": REDUCED}
PER_LAYER = [
    {"name": name, "unit": unit, "better": better, "source": "device_trace",
     "layer": layer, "moves": "step_ms_p50", "workloads": [CELL]}
    for name, unit, better, layer in MINE]

TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24,
            qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
            first_expert_held=4, published={"n_routed_experts": 16},
            vocab_size=256, sequence_length=128, flash_blocks=[32, 32],
            flash_interpret=True)

# XingChen-AGI/Xing4.0-29B-A4B's config.json as the catalog has it
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}


def _real():
    return mf.cell(mf.load(), CELL)["config"]


def _tiny_config(**changes):
    config = copy.deepcopy(_real())
    config.update(copy.deepcopy(TINY))
    config.update(changes)
    return config


def test_the_manifest_with_the_fifteenth_cell_is_sound():
    manifest = mf.load()
    assert mf.check(manifest) == []
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": NAME, "traffic": "ssgd_mhc_4k_1chip", "chips": 1}
    for word in ("4,096", "10 branches", "4 streams of 3,584", "5 latent cores",
                 "192 on 128", "YaRN", "2,048 of 16,384", "8 held", "8x", "5/40"):
        assert word in cell["why"], word
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry == {**CONFIG, "why": entry["why"]}
    for word in ("4 residual streams", "maps the layer computes", "mHC",
                 "20 Sinkhorn passes", "latent attention", "192 on 128", "YaRN",
                 "top-4-of-64", "8 experts", "1/8 vocab", "5 layers", "no MTP"):
        assert word in entry["why"], word
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == PER_LAYER
    assert sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", []) and m not in mine) == sorted(JOINED)
    assert len(manifest["configs"]) >= 14 and len(manifest["workloads"]) >= 15
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # additions at the ends: the fourteenth cell's entries stand right before
    at = [w["name"] for w in manifest["workloads"]].index(CELL)
    assert manifest["workloads"][at - 1]["name"] == "kimi_linear_48b_a3b.ssgd_kda_1chip"
    assert manifest["configs"][at - 2]["name"] == "kimi_linear_48b_a3b"
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[names.index("mhc_ms") - 1] == "nope_mla_proj_ms"
    for metric in manifest["per_layer"]:
        if metric["name"] in JOINED:
            assert metric["workloads"].index(CELL) >= 1  # behind what was there
        if metric["name"] in ("mla_core_ms", "mla_proj_ms", "mtp_ms",
                              "nope_mla_core_ms", "flash_core_ms"):
            assert CELL not in metric["workloads"]  # the cell has its own


def test_the_configuration_is_the_catalogs_but_for_its_cut():
    manifest = mf.load()
    config = _real()
    differs = sorted(k for k, v in CATALOG.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == sorted(REDUCED)
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == (
        5, 8, 16384, 0)
    assert config["published"] == {k: CATALOG[k] for k in config["reduced"]}
    # one chip's eighth of a layer's experts and of the rows
    assert config["n_routed_experts"] * 8 == CATALOG["n_routed_experts"]
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    # every width is the published one, the leading dense layers' key among
    # them: one of the two is run, and `dense_layers_run` says so
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["q_lora_rank"],
            config["kv_lora_rank"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["num_experts_per_tok"], config["routed_scaling_factor"],
            config["hc_mult"], config["hc_sinkhorn_iters"], config["hc_eps"],
            config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]) == (
        3584, 9216, 1024, 768, 512, 128, 64, 128, 4, 2, 4, 20, 1e-6, -30, 30)
    assert config["rope_scaling"] == CATALOG["rope_scaling"]
    assert (config["first_k_dense_replace"], config["dense_layers_run"]) == (2, 1)
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["source"] == config["source"] and entry["source"].endswith(
        "XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json")
    assert "8 chips share each layer's 64 routed experts" in config["deployment"]
    assert "16,384 of 131,072 rows a chip" in config["deployment"]
    assert "layers 5 to 39" in config["deployment"] and "72 %" in config["deployment"]
    assert "the multi-token-prediction module with the last of them" in (
        config["deployment"])
    assert len(config["assumed"]) >= 20
    for word in ("arXiv:2512.24880", "arXiv:2409.19606", "column sums + hc_eps",
                 "T_r(T_c(M))", "without a learned weight", "entry by copies",
                 "exit by the sum", "module under streams", "factor 2",
                 "starts them at 0.01", "neither uniform nor the identity",
                 "the config states none of this", "rotate-half",
                 "mscale^2 / sqrt(192)", "1.4159", "beta_fast 32", "noaux_tc",
                 "routers_trained", "normal(0, 0.02)", "uniform", "3e-4",
                 "recomputed_layer_types", "15.19e9"):
        assert any(word in line for line in config["assumed"]), word
    assert config["sequence_length"] == 4096 == (
        config["rope_scaling"]["original_max_position_embeddings"])
    assert config["routers_trained"] is False and config["first_expert_held"] == 0
    assert config["recomputed_layer_types"] == ["dense", "sparse"]
    traffic = mf.cell(manifest, CELL)["traffic"]
    assert (traffic["per_chip_batch"], traffic["pool"], traffic["mesh"]) == (
        1, 8, {"dp": 1})
    assert (traffic["launcher"], traffic["step"], traffic["placement"]) == (
        "none", "ssgd", "shard_batch")
    assert traffic["optimizer"] == {"name": "adamw", "learning_rate": 0.0003}


def test_the_cut_holds_the_parameters_its_file_says():
    """ISSUE 71's count, by `eval_shape`, against the file's `parameters` and
    `state_bytes`: 28.41 M a mixer, 344,091 the maps of a branch, 128.20 M
    the dense layer, 128.43 M an expert layer; with the module 154.13 M
    more."""
    config = _real()
    state = jax.eval_shape(lambda: xing4_0.init(config, 0))

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    assert xing4_0.layer_types(config) == ["dense"] + ["sparse"] * 4
    dense, sparse = state["layers"]
    assert dense["hc1_phi"].shape == (1, 4 * 3584, 24)
    assert sparse["hc2_phi"].shape == (4, 14336, 24)
    assert sparse["hc1_a"].shape == (4, 3) and sparse["hc1_b"].shape == (4, 24)
    assert sparse["w_q_down"].shape == (4, 3584, 768)
    assert sparse["w_q_up"].shape == (4, 768, 32 * 192)
    assert sparse["w_kv_down"].shape == (4, 3584, 512 + 64)
    assert sparse["w_kv_up"].shape == (4, 512, 32 * 256)
    assert sparse["wo"].shape == (4, 4096, 3584)
    assert sparse["router"].shape == (4, 3584, 64)
    assert sparse["router_bias"].shape == (4, 64)
    assert sparse["w_gate"].shape == sparse["w_up"].shape == (4, 8, 3584, 1024)
    assert dense["w_gate"].shape == (1, 3584, 9216)
    assert size({k: dense[k] for k in (
        "w_q_down", "q_latent_norm", "w_q_up", "w_kv_down", "kv_latent_norm",
        "w_kv_up", "wo")}) == 28_411_136
    assert size({k: dense[k] for k in ("hc1_phi", "hc1_a", "hc1_b")}) == 344_091
    assert size(dense) == 128_196_918 and size(sparse) / 4 == 128_426_358
    assert size(state["embed"]) == size(state["lm_head"]) == 58_720_256
    assert "mtp" not in state
    assert size(state) == config["parameters"] == 759_346_446
    assert 16 * size(state) == config["state_bytes"] == 12_149_543_136
    assert 0.71 < config["state_bytes"] / 16.91e9 < 0.73  # 72 % of the chip
    with_module = jax.eval_shape(
        lambda: xing4_0.init({**config, "num_nextn_predict_layers": 1}, 0))
    assert size(with_module["mtp"]) == 154_127_222
    assert size(with_module) == 913_473_668
    mc = xing4_0.model_config(config)
    assert mc.experts_held == (0, 8) and mc.n_experts == 64 and mc.top_k == 4
    assert all(kind.layer_remat and kind.streams == 4 for kind, _ in mc.stacks)
    assert mc.latent_dims == (768, 512, 128, 64, 128) and mc.mtp_depth == 0
    assert mc.yarn == (64.0, 4096, 32, 1, 1.0)
    mscale = 0.1 * np.log(64) + 1
    assert mscale == pytest.approx(1.4159, abs=1e-4)
    assert mc.attention_multiplier == pytest.approx(mscale ** 2 / np.sqrt(192))


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("norm_topk_prob", False),
    ("scoring_func", "softmax"), ("rope_scaling", None), ("n_group", 8),
    ("num_nextn_predict_layers", 2), ("n_shared_experts", 2), ("hc_mult", 1),
    ("dense_layers_run", 0)])
def test_the_family_refuses_a_layer_it_does_not_run(key, value):
    with pytest.raises(ValueError, match="as published"):
        xing4_0.model_config(_tiny_config(**{key: value}))


# --- operation and byte counts, by hand --------------------------------------

def test_the_residual_paths_operations_and_bytes_by_hand():
    real = _real()
    assert xing4_0.branches(real) == 10
    assert xing4_0.branches({**real, "num_nextn_predict_layers": 1}) == 12
    # a position and branch: the product with Phi, u of 4 streams, X' of 16 + 4
    assert xing4_0.hc_flops_per_token(real) == (
        2 * 14336 * 24 + 2 * 4 * 3584 + 2 * 20 * 3584)
    # 41 rows of 3,584 bfloat16 a position and branch: 12.04 GB a step
    assert xing4_0.stream_bytes_per_sample(real) == 41 * 3584 * 2 * 4096
    assert 10 * xing4_0.stream_bytes_per_sample(real) == pytest.approx(12.04e9, rel=1e-3)
    assert xing4_0.core_flops_per_sample(real) == (
        6 * (4096 * 4096 / 2) * 32 * (192 + 128)) == pytest.approx(0.5154e12, rel=1e-3)
    assert xing4_0.core_bytes_per_sample(real) == (
        6 * (192 + 128) * 32 * 4096 * 2) == pytest.approx(0.5033e9, rel=1e-3)
    peaks = harness.load_peaks("TPU v5 lite")
    # the softmax core is bound by its operations at 4,096 positions
    assert (xing4_0.core_flops_per_sample(real) / peaks["bf16_flops"]
            > xing4_0.core_bytes_per_sample(real) / peaks["hbm_bytes_per_s"])


def test_flops_per_sample_by_hand():
    """Per token at the tests' size: a latent mixer's five matrices, the
    dense feed-forward or the router over 16 (two passes where it is not
    trained), the shared expert and 4 x 8 / 16 of a routed expert, the head
    256 x 64, and the residual path of ten branches; 2 operations a
    multiply-add; five causal cores."""
    config = _tiny_config()
    mla = 64 * 24 + 24 * 4 * 32 + 64 * (16 + 8) + 16 * 4 * (24 + 16) + 4 * 16 * 64
    assert xing4_0.mixer_params_per_token(config) == mla
    assert xing4_0.expected_expert_passes(config) == 2.0
    expert = 3 * 64 * 32
    sparse = 64 * 16 + expert + 2.0 * expert
    layers = 256 * 64 + 5 * mla + 3 * 64 * 128 + 4 * sparse
    assert xing4_0.matmul_params_per_token(config) == layers + 10 * 256 * 24
    cores = 5 * 6 * (128 * 128 / 2) * 4 * (32 + 16)
    paths = 3 * 10 * (2 * 256 * 24 + 2 * 4 * 64 + 2 * 20 * 64) * 128
    assert config["routers_trained"] is False
    assert xing4_0.flops_per_sample(config) == (
        2 * (3 * layers - 4 * 64 * 16) * 128 + cores + paths)
    assert xing4_0.flops_per_sample({**config, "routers_trained": True}) == (
        2 * 3 * layers * 128 + cores + paths)
    real = _real()
    assert xing4_0.expected_expert_passes(real) == 0.5  # of a token's 4
    # the matrix products 9.0 TFLOP, five cores 2.6, the residual path 0.1
    assert xing4_0.flops_per_sample(real) == pytest.approx(11.69e12, rel=1e-3)


def test_the_multiplying_parameters_are_the_initialised_trees():
    real = _real()
    state = jax.eval_shape(lambda: xing4_0.init(real, 0))
    dense, sparse = state["layers"]
    size = lambda stack, *names: sum(stack[n].size for n in names)
    assert xing4_0.mixer_params_per_token(real) == size(
        dense, "w_q_down", "w_q_up", "w_kv_down", "w_kv_up", "wo")
    assert 4 * xing4_0.router_params_per_token(real) == size(sparse, "router")
    assert xing4_0.matmul_params_per_token(real) == (
        state["lm_head"].size + 5 * xing4_0.mixer_params_per_token(real)
        + size(dense, "w_gate", "w_up", "w_down", "hc1_phi", "hc2_phi")
        + size(sparse, "router", "shared_gate", "shared_up", "shared_down",
               "hc1_phi", "hc2_phi")
        + 4 * 0.5 * 3 * 3584 * 1024)


def test_host_batches_come_from_the_seed_uniform_over_the_slice():
    config = _tiny_config(sequence_length=4096)
    a = xing4_0.host_batch(config, 2**31 + 11, 3, 2)
    b = xing4_0.host_batch(config, 2**31 + 11, 3, 2)
    c = xing4_0.host_batch(config, 2**31 + 12, 3, 2)
    assert a.shape == (2, 4097) and a.dtype == np.int32  # S + 1 ids
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < 256
    counts = np.bincount(a.ravel(), minlength=256)
    assert counts.min() > 5 and counts.max() < 80 and 118 < np.median(a) < 138
    # with the module a row is S + 2 ids
    assert xing4_0.host_batch({**config, "num_nextn_predict_layers": 1},
                              1, 0, 2).shape == (2, 4098)
    real = xing4_0.host_batch(_real(), 2**31 + 11, 0, 1)
    assert real.shape == (1, 4097) and 16300 < real.max() < 16384


# --- the program against the reference --------------------------------------

def test_the_reference_imports_nothing_of_the_program_or_of_another_reference():
    import benchmark.reference.xing4_0 as reference

    with open(reference.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports
                            if "kungfu_tpu" in line or "benchmark" in line]
    assert "pallas" not in text and "custom_vjp" not in text
    assert 'default_matmul_precision("highest")' in text and "lax.top_k" in text
    assert "Departures from the papers" in text
    assert 'for _ in range(hyper["sinkhorn_iters"])' in text  # the loop written out
    assert xing4_0.REFERENCE_SAMPLES == 1


def test_the_real_program_holds_to_its_declared_precision():
    """At ISSUE 71's sizes, traced and not run: the state float32, the loss
    and every product over the 16,384 rows of the head float32; a bfloat16
    head is caught; and the maps' product with Phi is a float32 product at
    the highest precision."""
    config = _real()
    assert xing4_0.head_width(config) == 16384 != config["sequence_length"]
    state = jax.eval_shape(lambda: xing4_0.init(config, 0))
    sample = xing4_0.host_batch(config, 0, 0, xing4_0.REFERENCE_SAMPLES)
    traced = xing4_0.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, 16384, traced.jaxpr, state, state) == []
    low = {**config, "head_dtype": "bfloat16"}
    faults = harness.precision_faults(low, 16384, traced.jaxpr, state, state)
    assert faults and all("float32" in fault for fault in faults)
    low = {**config, "param_dtype": "bfloat16"}
    assert harness.precision_faults(low, 16384, traced.jaxpr, state, state)
    products = [eqn for eqn in harness.eqns_of(traced.jaxpr.jaxpr)
                if eqn.primitive.name == "dot_general"
                and "hc_maps" in str(eqn.source_info.name_stack)]
    assert products
    for eqn in products:
        assert {str(v.aval.dtype) for v in (*eqn.invars, *eqn.outvars)} == {"float32"}
        assert "HIGHEST" in str(eqn.params["precision"])


# --- the readers on a drawn trace ---------------------------------------

MS = 8_000_000  # a unit of the drawing below, in ns: 8 ms
# Two steps of 50 units on one chip, each alike (forward: entry, a layer's
# mixer branch and its expert branch, exit, the head; then the layer run
# again and its backward pass, the optimizer, a stray op):
STEP_OPS = [("embed", 0, 0.5), ("hc.in", 0.5, 0.75),
            ("maps1", 0.75, 1.75), ("read1", 1.75, 2.0),
            ("mla.down", 2.0, 2.5), ("mla.norm", 2.5, 2.75), ("mla.up", 2.75, 3.75),
            ("rope", 3.75, 4.0), ("mla.core", 4.0, 6.0), ("mla.out", 6.0, 6.5),
            ("write1", 6.5, 7.0), ("maps2", 7.0, 8.0), ("read2", 8.0, 8.25),
            ("router", 8.25, 8.75), ("gather", 8.75, 9.75),
            ("gmm.fwd", 9.75, 11.75), ("scatter", 11.75, 12.75),
            ("shared", 12.75, 13.25), ("write2", 13.25, 13.75),
            ("hc.out", 13.75, 14.0),
            ("head", 14.0, 16.0), ("head.bwd", 16.0, 19.0),
            ("hc.out.bwd", 19.0, 19.25),
            ("maps2.again", 19.25, 20.25), ("read2.again", 20.25, 20.5),
            ("write2.bwd", 20.5, 21.5), ("gmm.bwd", 21.5, 25.5),
            ("gather.bwd", 25.5, 27.5), ("read2.bwd", 27.5, 28.0),
            ("maps2.bwd", 28.0, 30.0),
            ("mla.up.again", 30.0, 31.0), ("write1.bwd", 31.0, 32.0),
            ("mla.core.dq", 32.0, 34.0), ("mla.core.dkv", 34.0, 37.0),
            ("mla.up.bwd", 37.0, 39.0), ("read1.bwd", 39.0, 39.5),
            ("maps1.bwd", 39.5, 41.5), ("hc.in.bwd", 41.5, 41.75),
            ("adamw", 41.75, 44.75), ("stray", 44.75, 45.25)]
DRAWN = {
    "chips": [{"plane": "/device:TPU:0", "program": "jit_step",
               "steps": [[0, 50 * MS], [50 * MS, 100 * MS]],
               "ops": [[name, int((at + a) * MS), int((at + b) * MS)]
                       for at in (0, 50) for name, a, b in STEP_OPS]}],
    "host": [], "lines": {},
}
FWD = "jit(local_step)/jvp()/while/body/closed_call"
BWD = "jit(local_step)/transpose(jvp())/while/body/closed_call/checkpoint"
AGAIN = f"{BWD}/rematted_computation"
SCOPES = {
    "embed": "jit(local_step)/jvp(embed)/gather",
    "hc.in": "jit(local_step)/jvp(hc_in)/tile",
    "maps1": f"{FWD}/hc/hc_maps/dot_general",
    "read1": f"{FWD}/hc/hc_read/mul",
    "mla.down": f"{FWD}/attn/mla_down/dot_general",
    "mla.norm": f"{FWD}/attn/mla_norm/mul",
    "mla.up": f"{FWD}/attn/mla_up/dot_general",
    "rope": f"{FWD}/attn/rope/pallas_call",
    "mla.core": f"{FWD}/attn/attn_latent/attn_core/pallas_call",
    "mla.out": f"{FWD}/attn/dot_general",
    "write1": f"{FWD}/hc/hc_write/add",
    "maps2": f"{FWD}/hc/hc_maps/dot_general",
    "read2": f"{FWD}/hc/hc_read/mul",
    "router": f"{FWD}/moe/moe_router/dot_general",
    "gather": f"{FWD}/moe/moe_dispatch/gather",
    "gmm.fwd": "ragged-dot-none",
    "scatter": f"{FWD}/moe/moe_combine/scatter-add",
    "shared": f"{FWD}/moe/moe_shared/dot_general",
    "write2": f"{FWD}/hc/hc_write/add",
    "hc.out": "jit(local_step)/jvp(hc_out)/add",
    "head": "jit(local_step)/jvp(head_loss)/dot_general",
    "head.bwd": "jit(local_step)/transpose(jvp(head_loss))/dot_general",
    "hc.out.bwd": "jit(local_step)/transpose(jvp(hc_out))/convert_element_type",
    "maps2.again": f"{AGAIN}/hc/hc_maps/dot_general",
    "read2.again": f"{AGAIN}/hc/hc_read/mul",
    "write2.bwd": f"{BWD}/hc/hc_write/mul",
    "gmm.bwd": "ragged-dot-none",
    "gather.bwd": f"{BWD}/moe/transpose(jvp(moe_dispatch))/scatter-add",
    "read2.bwd": f"{BWD}/hc/hc_read/mul",
    "maps2.bwd": f"{BWD}/hc/hc_maps/while/body/div",
    "mla.up.again": f"{AGAIN}/attn/mla_up/dot_general",
    "write1.bwd": f"{BWD}/hc/hc_write/mul",
    "mla.core.dq": f"{BWD}/attn/attn_latent/attn_core/pallas_call",
    "mla.core.dkv": f"{BWD}/attn/attn_latent/attn_core/pallas_call",
    "mla.up.bwd": f"{BWD}/attn/mla_up/dot_general",
    "read1.bwd": f"{BWD}/hc/hc_read/mul",
    "maps1.bwd": f"{BWD}/hc/hc_maps/dot_general",
    "hc.in.bwd": "jit(local_step)/transpose(jvp(hc_in))/reduce_sum",
    "adamw": "jit(local_step)/optimizer/optimizer_update/add",
}
READERS = (mhc_ms, mhc_maps_ms, mhc_stream_roofline_pct, yarn_mla_core_ms,
           yarn_mla_core_roofline_pct, yarn_mla_proj_ms)
MAPS = 1 + 1 + 1 + 2 + 2  # maps1, maps2, maps2 again, and the two backward
STREAMS = 0.25 + 0.5 + 0.25 + 0.5 + 0.25 + 1 + 0.5 + 1 + 0.5  # reads and writes


def _record(samples_per_step=1):
    return {"workload": CELL, "scopes": SCOPES, "samples_per_step": samples_per_step,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_drawn_times():
    record = _record()
    assert mhc_maps_ms.read(record, DRAWN) == pytest.approx(8 * MAPS)
    # all of the residual path: the maps, the mixings, entry and exit each way
    assert mhc_ms.read(record, DRAWN) == pytest.approx(
        8 * (MAPS + STREAMS + 4 * 0.25))
    assert yarn_mla_core_ms.read(record, DRAWN) == pytest.approx(8 * (2 + 2 + 3))
    # `attn` less the core: the projections, the latents' norm and the
    # rotary pass, each way; none of the residual path
    assert yarn_mla_proj_ms.read(record, DRAWN) == pytest.approx(
        8 * (0.5 + 0.25 + 1 + 0.25 + 0.5 + 1 + 2))


def test_drawn_shares_of_the_rooflines():
    """At the real widths: one sequence of 4,096 tokens a step, ten branches
    bound by their bytes and five cores bound by their operations."""
    record = _record()
    peaks = harness.load_peaks("TPU v5 lite")
    real = _real()
    moved = 10 * xing4_0.stream_bytes_per_sample(real) / peaks["hbm_bytes_per_s"]
    assert moved == pytest.approx(14.7e-3, rel=1e-2)
    assert mhc_stream_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * moved / (8e-3 * STREAMS))
    cores = 5 * xing4_0.core_flops_per_sample(real) / peaks["bf16_flops"]
    assert yarn_mla_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * cores / 56e-3)
    for reader in (mhc_stream_roofline_pct, yarn_mla_core_roofline_pct):
        assert reader.read(_record(2), DRAWN) == pytest.approx(
            2 * reader.read(record, DRAWN))
        assert 0 < reader.read(record, DRAWN) < 100


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
              "program_memory": {"total_bytes": 17_040_000_000},
              "memory_stats_peak_bytes": 1, "correct": True, "attempted": 20,
              "failed": 0}
    line = end_to_end.result_line(record, DRAWN, manifest)
    mine = {x["name"] for x in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(line["metrics"]) == mine
    assert {r.__name__.split(".")[-1] for r in READERS} <= mine
    assert set(JOINED) <= mine
    assert not {"mla_core_ms", "mla_proj_ms", "mtp_ms", "nope_mla_core_ms",
                "flash_core_ms", "moe_sigmoid_ms", "attn_proj_ms"} & mine
    value = lambda name: line["metrics"][name]["value"]
    # the accepted readers the cell joins, on this cell's scopes
    assert value("optimizer_ms") == pytest.approx(8 * 3.0)
    assert value("head_loss_ms") == pytest.approx(8 * (2 + 3))
    assert value("moe_ms") == pytest.approx(8 * (0.5 + 1 + 2 + 1 + 0.5 + 4 + 2))
    assert value("expert_ffn_ms") == pytest.approx(8 * (2 + 4))
    assert value("moe_dispatch_ms") == pytest.approx(8 * (0.5 + 1 + 1 + 2))
    # the six that claim device time leave the embedding and the stray op
    step = sum(b - a for _, a, b in STEP_OPS)
    claimed = sum(value(name) for name in (
        "mhc_ms", "yarn_mla_core_ms", "yarn_mla_proj_ms", "moe_ms",
        "head_loss_ms", "optimizer_ms"))
    assert 8 * step - claimed == pytest.approx(8 * (0.5 + 0.5))
    assert line["metrics"]["mhc_stream_roofline_pct"]["unit"] == "%"
