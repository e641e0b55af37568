"""The Keye-VL-2 family, its configuration and its six readers (PR 61): the
configuration file against the catalog's numbers, the parameter and operation
counts against the initialised tree and sums made by hand, the batches, the
declared precision of the program at the real sizes, and the readers, the new
six and the five the cell joined, against a drawn trace."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.families import keye_vl2
from benchmark.layer_metrics import (dsa_core_ms, dsa_core_roofline_pct,
                                     dsa_index_ms, dsa_index_roofline_pct,
                                     dsa_kl_ms, dsa_mix_ms)
from drawn_setup import drawn_setup

CELL = "keye_vl_2_0_30b_a3b.ssgd_dsa_1chip"
NAME = "keye_vl_2_0_30b_a3b"
MINE = (("dsa_core_ms", "ms", "lower", "Kernels"),
        ("dsa_core_roofline_pct", "%", "higher", "Kernels"),
        ("dsa_index_ms", "ms", "lower", "Kernels"),
        ("dsa_index_roofline_pct", "%", "higher", "Kernels"),
        ("dsa_kl_ms", "ms", "lower", "Model"),
        ("dsa_mix_ms", "ms", "lower", "Model"))
# accepted readers of scopes this cell's program has, whose lists it joins
JOINED = ("optimizer_ms", "head_loss_ms", "moe_ms", "expert_ffn_ms",
          "moe_dispatch_ms")
CONFIG = {
    "name": NAME,
    "source": "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json",
    "file": "benchmark/configs/keye_vl_2_0_30b_a3b.json",
    "reduced": ["num_hidden_layers", "num_experts", "num_local_experts",
                "vocab_size"]}
PER_LAYER = [
    {"name": name, "unit": unit, "better": better, "source": "device_trace",
     "layer": layer, "moves": "step_ms_p50", "workloads": [CELL]}
    for name, unit, better, layer in MINE]

TINY = dict(hidden_size=64, moe_intermediate_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            sa_config=dict(indexer_head_dim=8, indexer_num_heads=2,
                           indexer_num_kv_heads=1, kv_chunk_size=32,
                           q_chunk_size=32, topk=16),
            num_experts=4, num_local_experts=4, first_expert_held=2,
            published={"num_experts": 8}, num_experts_per_tok=3, vocab_size=320,
            sequence_length=64, flash_blocks=[32, 32], flash_interpret=True)

# Kwai-Keye/Keye-VL-2.0-30B-A3B's config.json as the catalog has it
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2", "moe_intermediate_size": 768,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def _real():
    return mf.cell(mf.load(), CELL)["config"]


def _tiny_config(**changes):
    config = copy.deepcopy(_real())
    config.update(copy.deepcopy(TINY))
    config.update(changes)
    return config


def test_the_manifest_with_the_twelfth_cell_is_sound():
    manifest = mf.load()
    assert mf.check(manifest) == []
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": NAME, "traffic": "ssgd_dsa_1chip", "chips": 1}
    for word in ("8,192", "6 indexers", "twice a step", "44 %",
                 "8,192 of 65,536", "16 held"):
        assert word in cell["why"], word
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry == {**CONFIG, "why": entry["why"]}
    for word in ("learned sparse attention", "2,048 keys", "KL", "top-8-of-128",
                 "share of 8", "1/8 vocabulary"):
        assert word in entry["why"], word
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == PER_LAYER
    assert sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", []) and m not in mine) == sorted(JOINED)
    assert len(manifest["configs"]) >= 11 and len(manifest["workloads"]) >= 12
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # additions at the ends: the eleventh cell's entries stand right before these
    at = [w["name"] for w in manifest["workloads"]].index(CELL)
    assert manifest["workloads"][at - 1]["name"] == "lfm2_24b_a2b.ssgd_conv_8k_1chip"
    assert manifest["configs"][at - 2]["name"] == "lfm2_24b_a2b"
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[names.index("dsa_core_ms") - 1] == "sconv_attn_core_roofline_pct"
    for metric in manifest["per_layer"]:
        if metric["name"] in JOINED:
            assert metric["workloads"].index(CELL) >= 1  # behind what was there
        if metric["name"] == "attn_proj_ms":  # would count the mechanism's scopes
            assert CELL not in metric["workloads"]


def test_the_configuration_is_the_catalogs_but_for_its_cut():
    manifest = mf.load()
    config = _real()
    differs = sorted(k for k, v in CATALOG.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["num_local_experts"], config["vocab_size"]) == (6, 16, 16, 18992)
    assert config["published"] == {k: CATALOG[k] for k in config["reduced"]}
    assert config["sa_config"] == CATALOG["sa_config"]  # untouched
    # one chip's eighth of an 8-chip layer, of the experts and of the rows
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["num_experts"] * 8 == CATALOG["num_experts"]
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["source"] == config["source"] and entry["source"].endswith(
        "Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert "8 chips" in config["deployment"] and len(config["assumed"]) >= 14
    assert "18,992 of 151,936 rows a chip" in config["deployment"]
    assert "sparse stage" in config["deployment"]
    for word in ("vision tower", "mrope_section", "LayerNorm", "q latent",
                 "indexer_num_kv_heads 1", "q_chunk_size", "dense warm-up",
                 "indexer_loss_weight 0.01", "scales no update", "norm a head",
                 "max_window_layers", "no auxiliary loss", "routers_trained",
                 "normal(0, 0.02)", "uniform", "3e-4", "compared_weights",
                 "recomputed_layer_types", "a byte a pair"):
        assert any(word in line for line in config["assumed"]), word
    assert config["sequence_length"] == 8192 and not config["tie_word_embeddings"]
    assert config["routers_trained"] is False and config["first_expert_held"] == 0
    assert config["indexer_loss_weight"] == 0.01  # ISSUE 61's 1.0: `assumed` says why
    assert set(config["compared_weights"]) == {"indexer_grads", "indexer_kl"}
    traffic = mf.cell(manifest, CELL)["traffic"]
    assert (traffic["per_chip_batch"], traffic["pool"], traffic["mesh"]) == (
        1, 8, {"dp": 1})
    assert (traffic["launcher"], traffic["step"], traffic["placement"]) == (
        "none", "ssgd", "shard_batch")
    assert traffic["optimizer"] == {"name": "adamw_warmup", "learning_rate": 0.0003,
                                    "warmup_steps": 2000}  # since PR 67


def test_the_cut_holds_the_parameters_its_file_says():
    """ISSUE 61's count, by `eval_shape`, against the file's `parameters` and
    `state_bytes`: 18.87 M a mixer, 2.26 M its indexer, 75.50 M the experts
    held of a layer, 96.90 M a layer."""
    config = _real()
    state = jax.eval_shape(lambda: keye_vl2.init(config, 0))

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    layers = state["layers"]
    assert size({k: layers[k] for k in ("wq", "wk", "wv", "wo", "q_norm_scale",
                                        "k_norm_scale")}) / 6 == 18_874_624
    assert size({k: layers[k] for k in keye_vl2.INDEX_LEAVES}) / 6 == 2_261_120
    assert layers["index_wq"].shape == (6, 2048, 1024)
    assert layers["index_wk"].shape == (6, 2048, 64)
    assert layers["index_w"].shape == (6, 2048, 16)
    assert layers["router"].shape == (6, 2048, 128)
    assert size({k: layers[k] for k in ("w_gate", "w_up", "w_down")}) / 6 == 75_497_472
    assert size(layers) / 6 == 96_899_456
    assert size(state["embed"]) == size(state["lm_head"]) == 38_895_616
    assert size(state) == config["parameters"] == 659_190_016
    assert 16 * size(state) == config["state_bytes"] == 10_547_040_256
    assert 0.62 < config["state_bytes"] / 16.9e9 < 0.63  # 62.4 % of the chip
    mc = keye_vl2.model_config(config)
    assert mc.experts_held == (0, 16) and mc.n_experts == 128 and mc.top_k == 8
    assert mc.sparse_index == (16, 64, 2048) and mc.layer_remat


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("use_sliding_window", True),
    ("decoder_sparse_step", 2), ("mlp_only_layers", [0]), ("hidden_act", "gelu"),
    ("rope_scaling", {"rope_type": "yarn"})])
def test_the_family_refuses_a_layer_it_does_not_run(key, value):
    with pytest.raises(ValueError, match="as published"):
        keye_vl2.model_config(_tiny_config(**{key: value}))


def test_the_family_refuses_a_key_a_head_of_the_indexer():
    config = _tiny_config()
    config["sa_config"]["indexer_num_kv_heads"] = 2
    with pytest.raises(ValueError, match="one indexer key"):
        keye_vl2.model_config(config)


# --- operation and byte counts, by hand --------------------------------------

def test_pairs_operations_and_bytes_by_hand():
    real = _real()
    # 2,048 rows take every earlier key, 6,144 rows choose 2,048: 44 %
    assert keye_vl2.chosen_pairs(real) == 2048 * 2049 // 2 + 6144 * 2048 == 14_681_088
    assert keye_vl2.causal_pairs(real) == 8192 * 8193 // 2 == 33_558_528
    assert 0.43 < keye_vl2.chosen_pairs(real) / keye_vl2.causal_pairs(real) < 0.44
    assert keye_vl2.core_flops_per_sample(real) == (
        6 * 2 * 14_681_088 * 32 * 128) == pytest.approx(0.7216e12, rel=1e-4)
    assert keye_vl2.core_bytes_per_sample(real) == (
        6 * (32 + 4) * 128 * 8192 * 2 + 3 * 33_558_528)
    assert keye_vl2.index_flops_per_sample(real) == (
        3 * 2 * 33_558_528 * 16 * 64) == pytest.approx(0.2062e12, rel=1e-3)
    assert keye_vl2.index_bytes_per_sample(real) == 4 * 4 * 33_558_528
    tiny = _tiny_config()
    assert keye_vl2.chosen_pairs(tiny) == 136 + 48 * 16
    full = _tiny_config()
    full["sa_config"]["topk"] = 64  # no query has more earlier keys
    assert keye_vl2.chosen_pairs(full) == keye_vl2.causal_pairs(full) == 2080


def test_flops_per_sample_by_hand():
    """Per token at the tests' size: the mixer's four projections, the
    indexer's three (two passes), the router over 8 (two passes where it is
    not trained), 3 x 4 / 8 of a routed expert, the head 320 x 64; 2
    operations a multiply-add; the core over 904 chosen pairs and the
    indexer's scores over 2,080 causal ones, a layer."""
    config = _tiny_config()
    mixer = 2 * 64 * 64 + 2 * 64 * 32
    indexer = 64 * (16 + 8 + 2)
    assert keye_vl2.mixer_params_per_token(config) == mixer
    assert keye_vl2.indexer_params_per_token(config) == indexer
    assert keye_vl2.expected_expert_passes(config) == 1.5
    experts = 1.5 * 3 * 64 * 32
    core = 6 * 2 * 904 * 4 * 16
    scores = 3 * 2 * 2080 * 2 * 8
    assert config["routers_trained"] is False
    per_token = 3 * 320 * 64 + 2 * (3 * (mixer + experts) + 2 * indexer + 2 * 64 * 8)
    assert keye_vl2.flops_per_sample(config) == (
        2 * per_token * 64 + 2 * (core + scores))
    assert keye_vl2.flops_per_sample({**config, "routers_trained": True}) == (
        2 * (per_token + 2 * 64 * 8) * 64 + 2 * (core + scores))
    real = _real()
    assert keye_vl2.expected_expert_passes(real) == 1.0
    # the chosen pairs' core 0.72 TFLOP and the scores 0.21 a layer: 14.9 TFLOP
    # a step, 76 ms of required work at the peak
    assert keye_vl2.flops_per_sample(real) == pytest.approx(14.93e12, rel=1e-3)


def test_the_multiplying_parameters_are_the_initialised_trees():
    """Every matrix of the initialised tree multiplies every token once, but
    the held experts (a token takes 8 x 16 / 128 of one on average) and the
    embedding (a lookup)."""
    real = _real()
    state = jax.eval_shape(lambda: keye_vl2.init(real, 0))
    layers = state["layers"]
    size = lambda *names: sum(layers[n].size for n in names) // 6
    assert keye_vl2.mixer_params_per_token(real) == size("wq", "wk", "wv", "wo")
    assert keye_vl2.indexer_params_per_token(real) == size(
        "index_wq", "index_wk", "index_w")
    assert keye_vl2.router_params_per_token(real) == size("router")
    assert keye_vl2.expert_params_per_token(real) * 16 == size(
        "w_gate", "w_up", "w_down")


def test_host_batches_come_from_the_seed_uniform_over_the_slice():
    config = _tiny_config(sequence_length=4096)
    a = keye_vl2.host_batch(config, 2**31 + 11, 3, 2)
    b = keye_vl2.host_batch(config, 2**31 + 11, 3, 2)
    c = keye_vl2.host_batch(config, 2**31 + 12, 3, 2)
    assert a.shape == (2, 4097) and a.dtype == np.int32  # S + 1 ids
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < 320
    counts = np.bincount(a.ravel(), minlength=320)
    assert counts.min() > 5 and counts.max() < 64 and 150 < np.median(a) < 170
    real = keye_vl2.host_batch(_real(), 2**31 + 11, 0, 1)
    assert real.shape == (1, 8193) and 18900 < real.max() < 18992


# --- the program against the reference --------------------------------------

def test_the_reference_imports_nothing_of_the_program_or_of_another_reference():
    import benchmark.reference.keye_vl2 as reference

    with open(reference.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports
                            if "kungfu_tpu" in line or "benchmark" in line]
    assert "pallas" not in text and "custom_vjp" not in text
    assert 'default_matmul_precision("highest")' in text and "lax.top_k" in text
    assert keye_vl2.REFERENCE_SAMPLES == 1


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One expert layer of 8 experts, 3 a token by softmax scores
    renormalised, cut into 8 shares of 1, no shared expert
    (`family_cases.shares_add_up`)."""
    import family_cases  # tests/: the shared case of every family with a share
    from benchmark.reference import keye_vl2 as reference
    from kungfu_tpu.models.transformer import TransformerConfig

    E, D, F, T = 8, 32, 16, 48
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    n = jax.random.normal(ks[0], (T, D))
    w = {"router": 0.5 * jax.random.normal(ks[1], (D, E)),
         "w_gate": 0.3 * jax.random.normal(ks[2], (E, D, F)),
         "w_up": 0.3 * jax.random.normal(ks[3], (E, D, F)),
         "w_down": 0.3 * jax.random.normal(ks[4], (E, F, D))}
    want, chosen = jax.jit(lambda n, w: reference.experts(n, w, dict(
        top_k=3, first_held=0, routers_trained=True)))(n, w)
    cfg = TransformerConfig(d_model=D, d_ff=F, dtype=jnp.float32, ffn="moe",
                            n_experts=E, top_k=3, gates="renorm")
    assert family_cases.shares_add_up(n, w, cfg, 1, want, chosen, 0.0) == 8


def test_the_real_program_holds_to_its_declared_precision():
    """At ISSUE 61's sizes, traced and not run: the state float32, the loss
    and every product over the 18,992 rows of the head float32; a bfloat16
    head is caught."""
    config = _real()
    assert keye_vl2.head_width(config) == 18992 != config["sequence_length"]
    state = jax.eval_shape(lambda: keye_vl2.init(config, 0))
    sample = keye_vl2.host_batch(config, 0, 0, keye_vl2.REFERENCE_SAMPLES)
    traced = keye_vl2.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, 18992, traced.jaxpr, state, state) == []
    low = {**config, "head_dtype": "bfloat16"}
    faults = harness.precision_faults(low, 18992, traced.jaxpr, state, state)
    assert faults and all("float32" in fault for fault in faults)
    low = {**config, "param_dtype": "bfloat16"}
    assert harness.precision_faults(low, 18992, traced.jaxpr, state, state)


# --- the readers on a drawn trace ---------------------------------------

MS = 8_000_000  # a unit of the drawing below, in ns: 8 ms
# Two steps of 60 units on one chip, each alike (forward, the head, then a
# layer run again and its backward pass, the optimizer, a stray op):
STEP_OPS = [("embed", 0, 0.5), ("qkv", 0.5, 1.5), ("rope", 1.5, 2),
            ("index.proj", 2, 2.5), ("index.fwd", 2.5, 4.5), ("select", 4.5, 7.5),
            ("core.fwd", 7.5, 9.5), ("mean.fwd", 9.5, 10.5), ("kl.fwd", 10.5, 11),
            ("wo", 11, 11.5), ("router", 11.5, 12), ("gmm.fwd", 12, 14),
            ("head", 14, 16.5), ("head.bwd", 16.5, 19.5),
            ("qkv.again", 19.5, 20.5), ("index.again", 20.5, 22.5),
            ("select.again", 22.5, 25.5), ("mean.again", 25.5, 26.5),
            ("gmm.bwd", 26.5, 30.5), ("wo.bwd", 30.5, 31.5), ("kl.bwd", 31.5, 32.5),
            ("core.dq", 32.5, 35.5), ("core.dkv", 35.5, 39.5),
            ("index.bwd", 39.5, 44.5), ("index.proj.bwd", 44.5, 45.5),
            ("qkv.bwd", 45.5, 47.5), ("adamw", 47.5, 50.5), ("stray", 50.5, 51)]
DRAWN = {
    "chips": [{"plane": "/device:TPU:0", "program": "jit_step",
               "steps": [[0, 60 * MS], [60 * MS, 120 * MS]],
               "ops": [[name, int((at + a) * MS), int((at + b) * MS)]
                       for at in (0, 60) for name, a, b in STEP_OPS]}],
    "host": [], "lines": {},
}
FWD = "jit(step)/shard_map/jvp()/while/body/checkpoint"
AGAIN = "jit(step)/shard_map/transpose(jvp())/while/body/checkpoint/rematted_computation"
BWD = "jit(step)/shard_map/transpose(jvp())/while/body/checkpoint"
SCOPES = {
    "embed": "jit(step)/shard_map/jvp(embed)/gather",
    "qkv": f"{FWD}/attn/attn_proj/dot_general",
    "rope": f"{FWD}/attn/attn_proj/rope/pallas_call",
    "index.proj": f"{FWD}/attn/dsa_index/dot_general",
    "index.fwd": f"{FWD}/attn/dsa_index/dsa_index_scores/pallas_call",
    "select": f"{FWD}/attn/dsa_select/while/body/reduce_sum",
    "core.fwd": f"{FWD}/attn/attn_sparse/attn_core/dsa_core_forward/pallas_call",
    "mean.fwd": f"{FWD}/attn/dsa_kl/dsa_head_mean_probs/pallas_call",
    "kl.fwd": f"{FWD}/attn/dsa_kl/reduce_sum",
    "wo": f"{FWD}/attn/attn_proj/dot_general",
    "router": f"{FWD}/moe/moe_router/dot_general",
    "gmm.fwd": "ragged-dot-none",
    "head": "jit(step)/shard_map/jvp(head_loss)/dot_general",
    "head.bwd": "jit(step)/shard_map/transpose(jvp(head_loss))/dot_general",
    "qkv.again": f"{AGAIN}/attn/attn_proj/dot_general",
    "index.again": f"{AGAIN}/attn/dsa_index/dsa_index_scores/pallas_call",
    "select.again": f"{AGAIN}/attn/dsa_select/while/body/reduce_sum",
    "mean.again": f"{AGAIN}/attn/dsa_kl/dsa_head_mean_probs/pallas_call",
    "gmm.bwd": "ragged-dot-none",
    "wo.bwd": f"{BWD}/attn/attn_proj/dot_general",
    "kl.bwd": f"{BWD}/attn/dsa_kl/sub",
    "core.dq": f"{BWD}/attn/attn_sparse/attn_core/dsa_core_dq/pallas_call",
    "core.dkv": f"{BWD}/attn/attn_sparse/attn_core/dsa_core_dkv/pallas_call",
    "index.bwd": f"{BWD}/attn/dsa_index/dsa_index_scores_bwd/pallas_call",
    "index.proj.bwd": f"{BWD}/attn/dsa_index/dot_general",
    "qkv.bwd": f"{BWD}/attn/attn_proj/dot_general",
    "adamw": "jit(step)/shard_map/optimizer/optimizer_update/add",
}
READERS = (dsa_core_ms, dsa_core_roofline_pct, dsa_index_ms,
           dsa_index_roofline_pct, dsa_kl_ms, dsa_mix_ms)


def _record(samples_per_step=1):
    return {"workload": CELL, "scopes": SCOPES, "samples_per_step": samples_per_step,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_drawn_times():
    record = _record()
    assert dsa_core_ms.read(record, DRAWN) == pytest.approx(8 * (2 + 3 + 4))
    # the projections, the scores each way and again, the choice twice
    assert dsa_index_ms.read(record, DRAWN) == pytest.approx(
        8 * (0.5 + 2 + 3 + 2 + 3 + 5 + 1))
    assert dsa_kl_ms.read(record, DRAWN) == pytest.approx(8 * (1 + 0.5 + 1 + 1))
    # `attn` less the three: projections and the rotary pass, each way, again
    assert dsa_mix_ms.read(record, DRAWN) == pytest.approx(
        8 * (1 + 0.5 + 0.5 + 1 + 1 + 2))


def test_drawn_shares_of_the_rooflines():
    """At the real widths: one sequence of 8,192 tokens a step, six layers,
    both bound by their operations."""
    record = _record()
    peaks = harness.load_peaks("TPU v5 lite")
    core = 6 * 12 * 14_681_088 * 32 * 128 / peaks["bf16_flops"]
    assert core > 6 * keye_vl2.core_bytes_per_sample(_real()) / peaks["hbm_bytes_per_s"]
    assert dsa_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * core / 72e-3)
    index = 6 * 6 * 33_558_528 * 1024 / peaks["bf16_flops"]
    assert index > 6 * 16 * 33_558_528 / peaks["hbm_bytes_per_s"]
    assert dsa_index_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * index / 132e-3)
    for reader in (dsa_core_roofline_pct, dsa_index_roofline_pct):
        assert reader.read(_record(2), DRAWN) == pytest.approx(
            2 * reader.read(record, DRAWN))
        assert 0 < reader.read(record, DRAWN) < 100
    # a record of another family's cell has no such layers to count
    other = {**record, "workload": "glm_4_7_flash.ssgd_mtp_8k_1chip"}
    assert dsa_core_roofline_pct.read(other, DRAWN) is None


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
              "program_memory": {"total_bytes": 16_720_000_000},
              "memory_stats_peak_bytes": 1, "correct": True, "attempted": 20,
              "failed": 0}
    line = end_to_end.result_line(record, DRAWN, manifest)
    mine = {x["name"] for x in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(line["metrics"]) == mine
    assert {r.__name__.split(".")[-1] for r in READERS} <= mine
    assert set(JOINED) <= mine
    assert not {"attn_proj_ms", "full_core_ms", "flash_core_ms",
                "moe_sigmoid_ms", "sconv_core_ms"} & mine
    value = lambda name: line["metrics"][name]["value"]
    # the accepted readers the cell joins, on this cell's scopes
    assert value("optimizer_ms") == pytest.approx(8 * 3.0)
    assert value("head_loss_ms") == pytest.approx(8 * (2.5 + 3))
    assert value("moe_ms") == pytest.approx(8 * (0.5 + 2 + 4))
    assert value("expert_ffn_ms") == pytest.approx(8 * (2 + 4))
    assert value("moe_dispatch_ms") == pytest.approx(8 * 0.5)
    # the eight that claim device time leave the embedding and the stray op
    step = sum(b - a for _, a, b in STEP_OPS)
    claimed = sum(value(name) for name in (
        "dsa_core_ms", "dsa_index_ms", "dsa_kl_ms", "dsa_mix_ms", "moe_ms",
        "head_loss_ms", "optimizer_ms"))
    assert 8 * step - claimed == pytest.approx(8 * (0.5 + 0.5))
    assert line["metrics"]["dsa_core_roofline_pct"]["unit"] == "%"
    assert mf.check_result_line(line, manifest, CELL, traced=True) == []
