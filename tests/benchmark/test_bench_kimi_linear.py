"""The Kimi Linear family, its configuration and its six readers (PR 69): the
configuration file against the catalog's numbers, the parameter and operation
counts against the initialised tree and sums made by hand, the batches, the
declared precision of the program at the real sizes, and the readers, the new
six and the five the cell joined, against a drawn trace."""

import copy

import jax
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.families import kimi_linear
from benchmark.layer_metrics import (kda_core_ms, kda_core_roofline_pct,
                                     kda_mix_ms, nope_mla_core_ms,
                                     nope_mla_core_roofline_pct,
                                     nope_mla_proj_ms)
from drawn_setup import drawn_setup

CELL = "kimi_linear_48b_a3b.ssgd_kda_1chip"
NAME = "kimi_linear_48b_a3b"
MINE = (("kda_core_ms", "ms", "lower", "Kernels"),
        ("kda_core_roofline_pct", "%", "higher", "Kernels"),
        ("kda_mix_ms", "ms", "lower", "Model"),
        ("nope_mla_core_ms", "ms", "lower", "Kernels"),
        ("nope_mla_core_roofline_pct", "%", "higher", "Kernels"),
        ("nope_mla_proj_ms", "ms", "lower", "Model"))
# accepted readers of scopes this cell's program has, whose lists it joins
JOINED = ("optimizer_ms", "head_loss_ms", "moe_ms", "expert_ffn_ms",
          "moe_dispatch_ms")
CONFIG = {
    "name": NAME,
    "source": "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json",
    "file": "benchmark/configs/kimi_linear_48b_a3b.json",
    "reduced": ["num_hidden_layers", "num_experts", "vocab_size"]}
PER_LAYER = [
    {"name": name, "unit": unit, "better": better, "source": "device_trace",
     "layer": layer, "moves": "step_ms_p50", "workloads": [CELL]}
    for name, unit, better, layer in MINE]

TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=4,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=8, num_experts=8, first_expert_held=4,
            num_experts_per_token=4, published={"num_experts": 16},
            vocab_size=256, sequence_length=128, flash_blocks=[32, 32],
            flash_interpret=True)

# moonshotai/Kimi-Linear-48B-A3B-Instruct's config.json as the catalog has it
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


def _real():
    return mf.cell(mf.load(), CELL)["config"]


def _tiny_config(**changes):
    config = copy.deepcopy(_real())
    config.update(copy.deepcopy(TINY))
    config["linear_attn_config"].update(num_heads=4, head_dim=16)
    config.update(changes)
    return config


def test_the_manifest_with_the_fourteenth_cell_is_sound():
    manifest = mf.load()
    assert mf.check(manifest) == []
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": NAME, "traffic": "ssgd_kda_1chip", "chips": 1}
    for word in ("16,384", "4 KDA mixers", "32 heads of 128", "1 latent core",
                 "192 on 128", "134 M pairs", "4,096 of 131,072", "8 held",
                 "32x", "5/27"):
        assert word in cell["why"], word
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry == {**CONFIG, "why": entry["why"]}
    for word in ("KDA", "decayed a key feature", "3:1", "latent attention",
                 "no q latent or positions", "192 on 128", "top-8-of-256",
                 "share of 32", "8 experts", "1/8 vocab", "5 layers"):
        assert word in entry["why"], word
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == PER_LAYER
    assert sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", []) and m not in mine) == sorted(JOINED)
    assert len(manifest["configs"]) >= 13 and len(manifest["workloads"]) >= 14
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # additions at the ends: the thirteenth cell's entries stand right before
    at = [w["name"] for w in manifest["workloads"]].index(CELL)
    assert manifest["workloads"][at - 1]["name"] == (
        "smallthinker_21b_a3b.ssgd_swa_nope_1chip")
    assert manifest["configs"][at - 2]["name"] == "smallthinker_21b_a3b"
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[names.index("kda_core_ms") - 1] == "reglu_moe_ms"
    for metric in manifest["per_layer"]:
        if metric["name"] in JOINED:
            assert metric["workloads"].index(CELL) >= 1  # behind what was there
        if metric["name"] in ("gdn_core_ms", "gdn_mix_ms", "mla_core_ms",
                              "mla_proj_ms", "flash_core_ms"):
            assert CELL not in metric["workloads"]  # the cell has its own


def test_the_configuration_is_the_catalogs_but_for_its_cut():
    manifest = mf.load()
    config = _real()
    differs = sorted(k for k, v in CATALOG.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 20480)
    assert config["published"] == {k: CATALOG[k] for k in config["reduced"]}
    # one chip's thirty-second of a layer's experts, an eighth of the rows
    assert config["num_experts"] * 32 == CATALOG["num_experts"]
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    # every width is the published one
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["num_experts_per_token"],
            config["routed_scaling_factor"]) == (
        2304, 9216, 1024, 512, 128, 64, 128, 8, 2.446)
    assert config["linear_attn_config"] == CATALOG["linear_attn_config"]
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["source"] == config["source"] and entry["source"].endswith(
        "moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert "32 chips share each layer's 256 routed experts" in config["deployment"]
    assert "20,480 of 163,840 rows a chip" in config["deployment"]
    assert "layers 5 to 26" in config["deployment"] and "57 %" in config["deployment"]
    assert len(config["assumed"]) >= 14
    for word in ("full_attn_layers", "l2(silu(conv4", "the log decay a key feature",
                 "no bias", "A uniform in (0, 16)", "mla_use_nope",
                 "q_lora_rank null", "sqrt(192)", "the one k_pe",
                 "nothing is turned", "moe_renormalize", "2.446",
                 "use_grouped_topk", "routers_trained", "normal(0, 0.02)",
                 "uniform", "3e-4", "recomputed_layer_types", "14.17 GB"):
        assert any(word in line for line in config["assumed"]), word
    assert config["sequence_length"] == 16384
    assert config["routers_trained"] is False and config["first_expert_held"] == 0
    assert config["recomputed_layer_types"] == ["kda_dense", "kda_sparse", "mla_sparse"]
    traffic = mf.cell(manifest, CELL)["traffic"]
    assert (traffic["per_chip_batch"], traffic["pool"], traffic["mesh"]) == (
        1, 8, {"dp": 1})
    assert (traffic["launcher"], traffic["step"], traffic["placement"]) == (
        "none", "ssgd", "shard_batch")
    assert traffic["optimizer"] == {"name": "adamw", "learning_rate": 0.0003}


def test_the_cut_holds_the_parameters_its_file_says():
    """ISSUE 69's count, by `eval_shape`, against the file's `parameters` and
    `state_bytes`: 39.51 M a KDA mixer, 29.11 M the latent one, 103.22 M
    layer 0, 103.81 M a KDA expert layer, 93.41 M the latent expert layer."""
    config = _real()
    state = jax.eval_shape(lambda: kimi_linear.init(config, 0))

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    assert kimi_linear.layer_types(config) == [
        "kda_dense", "kda_sparse", "kda_sparse", "mla_sparse", "kda_sparse"]
    dense, sparse, full, last = state["layers"]
    assert dense["w_q"].shape == (1, 2304, 4096) and sparse["w_q"].shape == (2, 2304, 4096)
    assert sparse["conv_k"].shape == (2, 4, 4096)
    assert sparse["w_f_a"].shape == (2, 2304, 128) and sparse["w_f_b"].shape == (2, 128, 4096)
    assert sparse["A_log"].shape == (2, 32) and sparse["dt_bias"].shape == (2, 4096)
    assert sparse["w_beta"].shape == (2, 2304, 32)
    assert full["w_q_up"].shape == (1, 2304, 32 * 192)
    assert full["w_kv_down"].shape == (1, 2304, 512 + 64)
    assert full["w_kv_up"].shape == (1, 512, 32 * 256)
    assert full["wo"].shape == last["wo"].shape == (1, 4096, 2304)
    assert full["router"].shape == (1, 2304, 256) and full["router_bias"].shape == (1, 256)
    assert sparse["w_gate"].shape == sparse["w_up"].shape == (2, 8, 2304, 1024)
    assert dense["w_gate"].shape == (1, 2304, 9216)
    kda_leaves = ("w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_f_a",
                  "w_f_b", "A_log", "dt_bias", "w_beta", "kda_norm_scale",
                  "w_g_a", "w_g_b", "wo")
    assert size({k: dense[k] for k in kda_leaves}) == 39_514_272
    assert size({k: full[k] for k in ("w_q_up", "w_kv_down", "kv_latent_norm",
                                      "w_kv_up", "wo")}) == 29_114_880
    assert size(dense) == 103_219_872
    assert size(sparse) / 2 == size(last) == 103_809_952
    assert size(full) == 93_410_560
    assert size(state["embed"]) == size(state["lm_head"]) == 47_185_920
    assert size(state) == config["parameters"] == 602_434_432
    assert 16 * size(state) == config["state_bytes"] == 9_638_950_912
    assert 0.56 < config["state_bytes"] / 16.91e9 < 0.58  # 57 % of the chip
    mc = kimi_linear.model_config(config)
    assert mc.experts_held == (0, 8) and mc.n_experts == 256 and mc.top_k == 8
    assert all(kind.layer_remat for kind, _ in mc.stacks)
    assert mc.latent_dims == (0, 512, 128, 64, 128) and mc.kda_heads == (32, 128)


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("moe_renormalize", False),
    ("moe_router_activation_func", "softmax"), ("q_lora_rank", 768),
    ("mla_use_nope", False), ("num_expert_group", 8),
    ("num_nextn_predict_layers", 1), ("num_shared_experts", 2)])
def test_the_family_refuses_a_layer_it_does_not_run(key, value):
    with pytest.raises(ValueError, match="as published"):
        kimi_linear.model_config(_tiny_config(**{key: value}))


# --- operation and byte counts, by hand --------------------------------------

def test_core_operations_and_bytes_by_hand():
    real = _real()
    assert kimi_linear.kda_core_flops_per_sample(real) == (
        3 * 7 * 128 * 128 * 32 * 16384) == pytest.approx(0.1804e12, rel=1e-3)
    assert kimi_linear.kda_core_bytes_per_sample(real) == 16384 * (
        11 * 4096 * 2 + 3 * 4096 * 4 + 3 * 32 * 4) == pytest.approx(2.288e9, rel=1e-3)
    assert kimi_linear.mla_core_flops_per_sample(real) == (
        6 * 134_217_728 * 32 * (192 + 128)) == pytest.approx(8.246e12, rel=1e-3)
    assert kimi_linear.mla_core_bytes_per_sample(real) == (
        6 * (192 + 128) * 32 * 16384 * 2) == pytest.approx(2.013e9, rel=1e-3)
    peaks = harness.load_peaks("TPU v5 lite")
    # the rule is bound by its bytes, the softmax core by its operations
    assert (kimi_linear.kda_core_bytes_per_sample(real) / peaks["hbm_bytes_per_s"]
            > kimi_linear.kda_core_flops_per_sample(real) / peaks["bf16_flops"])
    assert (kimi_linear.mla_core_flops_per_sample(real) / peaks["bf16_flops"]
            > kimi_linear.mla_core_bytes_per_sample(real) / peaks["hbm_bytes_per_s"])


def test_flops_per_sample_by_hand():
    """Per token at the tests' size: a KDA mixer's projections, taps and low
    ranks, the latent mixer's four matrices, the dense feed-forward or the
    router over 16 (two passes where it is not trained), the shared expert
    and 4 x 8 / 16 of a routed expert, the head 256 x 64; 2 operations a
    multiply-add; four rules and one causal core."""
    config = _tiny_config()
    kda = 3 * 64 * 64 + 3 * 4 * 64 + 2 * (64 * 16 + 16 * 64) + 64 * 4 + 64 * 64
    mla = 64 * 4 * 24 + 64 * (16 + 8) + 16 * 4 * (16 + 8) + 4 * 8 * 64
    assert kimi_linear.mixer_params_per_token(config, kimi_linear.KDA) == kda
    assert kimi_linear.mixer_params_per_token(config, kimi_linear.MLA) == mla
    assert kimi_linear.expected_expert_passes(config) == 2.0
    expert = 3 * 64 * 32
    sparse = 64 * 16 + expert + 2.0 * expert
    matmul = 256 * 64 + 4 * kda + mla + 3 * 64 * 128 + 4 * sparse
    assert kimi_linear.matmul_params_per_token(config) == matmul
    cores = 4 * 3 * 7 * 16 * 16 * 4 * 128 + 6 * (128 * 128 / 2) * 4 * (24 + 8)
    assert config["routers_trained"] is False
    assert kimi_linear.flops_per_sample(config) == (
        2 * (3 * matmul - 4 * 64 * 16) * 128 + cores)
    assert kimi_linear.flops_per_sample({**config, "routers_trained": True}) == (
        2 * 3 * matmul * 128 + cores)
    real = _real()
    assert kimi_linear.expected_expert_passes(real) == 0.25  # of a token's 8
    # the matrix products 32.9 TFLOP, the latent core 8.2, four rules 0.7
    assert kimi_linear.flops_per_sample(real) == pytest.approx(41.90e12, rel=1e-3)


def test_the_multiplying_parameters_are_the_initialised_trees():
    """Every matrix of the initialised tree multiplies every token once, but
    the held experts (a token takes 8 x 8 / 256 of one on average) and the
    embedding (a lookup); A_log, dt_bias and the norms' scales do no matmul."""
    real = _real()
    state = jax.eval_shape(lambda: kimi_linear.init(real, 0))
    dense, _, full, _ = state["layers"]
    size = lambda stack, *names: sum(stack[n].size for n in names)
    assert kimi_linear.mixer_params_per_token(real, kimi_linear.KDA) == size(
        dense, "w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_f_a",
        "w_f_b", "w_beta", "w_g_a", "w_g_b", "wo")
    assert kimi_linear.mixer_params_per_token(real, kimi_linear.MLA) == size(
        full, "w_q_up", "w_kv_down", "w_kv_up", "wo")
    assert kimi_linear.router_params_per_token(real) == size(full, "router")


def test_host_batches_come_from_the_seed_uniform_over_the_slice():
    config = _tiny_config(sequence_length=4096)
    a = kimi_linear.host_batch(config, 2**31 + 11, 3, 2)
    b = kimi_linear.host_batch(config, 2**31 + 11, 3, 2)
    c = kimi_linear.host_batch(config, 2**31 + 12, 3, 2)
    assert a.shape == (2, 4097) and a.dtype == np.int32  # S + 1 ids
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < 256
    counts = np.bincount(a.ravel(), minlength=256)
    assert counts.min() > 5 and counts.max() < 80 and 118 < np.median(a) < 138
    real = kimi_linear.host_batch(_real(), 2**31 + 11, 0, 1)
    assert real.shape == (1, 16385) and 20300 < real.max() < 20480


# --- the program against the reference --------------------------------------

def test_the_reference_imports_nothing_of_the_program_or_of_another_reference():
    import benchmark.reference.kimi_linear as reference

    with open(reference.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports
                            if "kungfu_tpu" in line or "benchmark" in line]
    assert "pallas" not in text and "custom_vjp" not in text
    assert 'default_matmul_precision("highest")' in text and "lax.top_k" in text
    assert "Departures from the report" in text
    assert kimi_linear.REFERENCE_SAMPLES == 1


def test_the_real_program_holds_to_its_declared_precision():
    """At ISSUE 69's sizes, traced and not run: the state float32, the loss
    and every product over the 20,480 rows of the head float32; a bfloat16
    head is caught."""
    config = _real()
    assert kimi_linear.head_width(config) == 20480 != config["sequence_length"]
    state = jax.eval_shape(lambda: kimi_linear.init(config, 0))
    sample = kimi_linear.host_batch(config, 0, 0, kimi_linear.REFERENCE_SAMPLES)
    traced = kimi_linear.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, 20480, traced.jaxpr, state, state) == []
    low = {**config, "head_dtype": "bfloat16"}
    faults = harness.precision_faults(low, 20480, traced.jaxpr, state, state)
    assert faults and all("float32" in fault for fault in faults)
    low = {**config, "param_dtype": "bfloat16"}
    assert harness.precision_faults(low, 20480, traced.jaxpr, state, state)


# --- the readers on a drawn trace ---------------------------------------

MS = 8_000_000  # a unit of the drawing below, in ns: 8 ms
# Two steps of 60 units on one chip, each alike (forward: a KDA layer and the
# latent layer with its experts, the head; then the layers run again and
# their backward passes, the optimizer, a stray op):
STEP_OPS = [("embed", 0, 0.5), ("kda.proj", 0.5, 2), ("kda.conv", 2, 3),
            ("kda.pairs", 3, 4), ("kda.solve", 4, 4.5), ("kda.fwd", 4.5, 6),
            ("kda.norm", 6, 6.5), ("kda.out", 6.5, 7),
            ("mla.down", 7, 7.5), ("mla.norm", 7.5, 7.75), ("mla.up", 7.75, 8.75),
            ("mla.core", 8.75, 11.75), ("mla.out", 11.75, 12.25),
            ("router", 12.25, 12.75), ("gather", 12.75, 13.75),
            ("gmm.fwd", 13.75, 15.75), ("scatter", 15.75, 16.75),
            ("shared", 16.75, 17.25),
            ("head", 17.25, 19.75), ("head.bwd", 19.75, 22.75),
            ("mla.up.again", 22.75, 23.75), ("gmm.bwd", 23.75, 27.75),
            ("gather.bwd", 27.75, 29.75), ("mla.core.dq", 29.75, 32.75),
            ("mla.core.dkv", 32.75, 36.75), ("mla.up.bwd", 36.75, 38.75),
            ("kda.proj.again", 38.75, 40.25), ("kda.pairs.again", 40.25, 41.25),
            ("kda.bwd", 41.25, 44.25), ("kda.pairs.bwd", 44.25, 46.25),
            ("kda.conv.bwd", 46.25, 47.25), ("kda.proj.bwd", 47.25, 50.25),
            ("adamw", 50.25, 53.25), ("stray", 53.25, 53.75)]
DRAWN = {
    "chips": [{"plane": "/device:TPU:0", "program": "jit_step",
               "steps": [[0, 60 * MS], [60 * MS, 120 * MS]],
               "ops": [[name, int((at + a) * MS), int((at + b) * MS)]
                       for at in (0, 60) for name, a, b in STEP_OPS]}],
    "host": [], "lines": {},
}
FWD = "jit(local_step)/jvp()/while/body/closed_call"
BWD = "jit(local_step)/transpose(jvp())/while/body/closed_call/checkpoint"
AGAIN = f"{BWD}/rematted_computation"
HEADS = "kda/while/body/checkpoint"
SCOPES = {
    "embed": "jit(local_step)/jvp(embed)/gather",
    "kda.proj": f"{FWD}/{HEADS}/kda_proj/dot_general",
    "kda.conv": f"{FWD}/{HEADS}/kda_conv/mul",
    "kda.pairs": f"{FWD}/{HEADS}/kda_core/kda_pairs",
    "kda.solve": f"{FWD}/{HEADS}/kda_core/gated_delta_solve",
    "kda.fwd": f"{FWD}/{HEADS}/kda_core/kda_forward",
    "kda.norm": f"{FWD}/{HEADS}/kda_norm/mul",
    "kda.out": f"{FWD}/{HEADS}/kda_proj/dot_general",
    "mla.down": f"{FWD}/attn/mla_down/dot_general",
    "mla.norm": f"{FWD}/attn/mla_norm/mul",
    "mla.up": f"{FWD}/attn/mla_up/dot_general",
    "mla.core": f"{FWD}/attn/attn_latent/attn_core/pallas_call",
    "mla.out": f"{FWD}/attn/dot_general",
    "router": f"{FWD}/moe/moe_router/dot_general",
    "gather": f"{FWD}/moe/moe_dispatch/gather",
    "gmm.fwd": "ragged-dot-none",
    "scatter": f"{FWD}/moe/moe_combine/scatter-add",
    "shared": f"{FWD}/moe/moe_shared/dot_general",
    "head": "jit(local_step)/jvp(head_loss)/dot_general",
    "head.bwd": "jit(local_step)/transpose(jvp(head_loss))/dot_general",
    "mla.up.again": f"{AGAIN}/attn/mla_up/dot_general",
    "gmm.bwd": "ragged-dot-none",
    "gather.bwd": f"{BWD}/moe/transpose(jvp(moe_dispatch))/scatter-add",
    "mla.core.dq": f"{BWD}/attn/attn_latent/attn_core/pallas_call",
    "mla.core.dkv": f"{BWD}/attn/attn_latent/attn_core/pallas_call",
    "mla.up.bwd": f"{BWD}/attn/mla_up/dot_general",
    "kda.proj.again": f"{BWD}/{HEADS}/rematted_computation/kda_proj/dot_general",
    "kda.pairs.again": f"{BWD}/{HEADS}/rematted_computation/kda_core/kda_pairs",
    "kda.bwd": f"{BWD}/{HEADS}/kda_core/kda_backward",
    "kda.pairs.bwd": f"{BWD}/{HEADS}/kda_core/kda_pairs_backward",
    "kda.conv.bwd": f"{BWD}/{HEADS}/kda_conv/mul",
    "kda.proj.bwd": f"{BWD}/{HEADS}/kda_proj/dot_general",
    "adamw": "jit(local_step)/optimizer/optimizer_update/add",
}
READERS = (kda_core_ms, kda_core_roofline_pct, kda_mix_ms, nope_mla_core_ms,
           nope_mla_core_roofline_pct, nope_mla_proj_ms)


def _record(samples_per_step=1):
    return {"workload": CELL, "scopes": SCOPES, "samples_per_step": samples_per_step,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_drawn_times():
    record = _record()
    # the pairs, the inverse and the scan forward; the pairs again, the scan
    # in reverse and the pairs' own backward pass
    assert kda_core_ms.read(record, DRAWN) == pytest.approx(
        8 * (1 + 0.5 + 1.5 + 1 + 3 + 2))
    # `kda` less the rule: projections, convolutions and the gated norm
    assert kda_mix_ms.read(record, DRAWN) == pytest.approx(
        8 * (1.5 + 1 + 0.5 + 0.5 + 1.5 + 1 + 3))
    assert nope_mla_core_ms.read(record, DRAWN) == pytest.approx(8 * (3 + 3 + 4))
    # `attn` less the core: the projections and the latent's norm, each way
    assert nope_mla_proj_ms.read(record, DRAWN) == pytest.approx(
        8 * (0.5 + 0.25 + 1 + 0.5 + 1 + 2))


def test_drawn_shares_of_the_rooflines():
    """At the real widths: one sequence of 16,384 tokens a step, four KDA
    layers bound by their bytes and one latent core bound by its operations."""
    record = _record()
    peaks = harness.load_peaks("TPU v5 lite")
    real = _real()
    rule = 4 * kimi_linear.kda_core_bytes_per_sample(real) / peaks["hbm_bytes_per_s"]
    assert kda_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * rule / 72e-3)
    core = kimi_linear.mla_core_flops_per_sample(real) / peaks["bf16_flops"]
    assert nope_mla_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * core / 80e-3)
    for reader in (kda_core_roofline_pct, nope_mla_core_roofline_pct):
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
              "program_memory": {"total_bytes": 14_170_000_000},
              "memory_stats_peak_bytes": 1, "correct": True, "attempted": 20,
              "failed": 0}
    line = end_to_end.result_line(record, DRAWN, manifest)
    mine = {x["name"] for x in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(line["metrics"]) == mine
    assert {r.__name__.split(".")[-1] for r in READERS} <= mine
    assert set(JOINED) <= mine
    assert not {"gdn_core_ms", "gdn_mix_ms", "mla_core_ms", "mla_proj_ms",
                "flash_core_ms", "moe_sigmoid_ms", "attn_proj_ms"} & mine
    value = lambda name: line["metrics"][name]["value"]
    # the accepted readers the cell joins, on this cell's scopes
    assert value("optimizer_ms") == pytest.approx(8 * 3.0)
    assert value("head_loss_ms") == pytest.approx(8 * (2.5 + 3))
    assert value("moe_ms") == pytest.approx(8 * (0.5 + 1 + 2 + 1 + 0.5 + 4 + 2))
    assert value("expert_ffn_ms") == pytest.approx(8 * (2 + 4))
    assert value("moe_dispatch_ms") == pytest.approx(8 * (0.5 + 1 + 1 + 2))
    # the seven that claim device time leave the embedding and the stray op
    step = sum(b - a for _, a, b in STEP_OPS)
    claimed = sum(value(name) for name in (
        "kda_core_ms", "kda_mix_ms", "nope_mla_core_ms", "nope_mla_proj_ms",
        "moe_ms", "head_loss_ms", "optimizer_ms"))
    assert 8 * step - claimed == pytest.approx(8 * (0.5 + 0.5))
    assert line["metrics"]["kda_core_roofline_pct"]["unit"] == "%"
