"""The SmallThinker family, its configuration and its seven readers (PR 65):
the configuration file against the catalog's numbers, the parameter and
operation counts against the initialised tree and sums made by hand, the
batches, the declared precision of the program at the real sizes, and the
readers, the new seven and the five the cell joined, against a drawn trace."""

import copy

import jax
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.families import smallthinker
from benchmark.layer_metrics import (early_router_ms, nope_full_core_ms,
                                     nope_full_core_roofline_pct, reglu_moe_ms,
                                     swa_attn_proj_ms, swa_core_ms,
                                     swa_core_roofline_pct)
from drawn_setup import drawn_setup

CELL = "smallthinker_21b_a3b.ssgd_swa_nope_1chip"
NAME = "smallthinker_21b_a3b"
MINE = (("early_router_ms", "ms", "lower", "Model"),
        ("swa_core_ms", "ms", "lower", "Kernels"),
        ("swa_core_roofline_pct", "%", "higher", "Kernels"),
        ("nope_full_core_ms", "ms", "lower", "Kernels"),
        ("nope_full_core_roofline_pct", "%", "higher", "Kernels"),
        ("swa_attn_proj_ms", "ms", "lower", "Model"),
        ("reglu_moe_ms", "ms", "lower", "Model"))
# accepted readers of scopes this cell's program has, whose lists it joins
JOINED = ("optimizer_ms", "head_loss_ms", "moe_ms", "expert_ffn_ms",
          "moe_dispatch_ms")
CONFIG = {
    "name": NAME,
    "source": "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json",
    "file": "benchmark/configs/smallthinker_21b_a3b.json",
    "reduced": ["num_hidden_layers", "moe_num_primary_experts", "vocab_size",
                "rope_layout", "sliding_window_layout"]}
PER_LAYER = [
    {"name": name, "unit": unit, "better": better, "source": "device_trace",
     "layer": layer, "moves": "step_ms_p50", "workloads": [CELL]}
    for name, unit, better, layer in MINE]

TINY = dict(hidden_size=64, moe_ffn_hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            sliding_window_size=16, rope_layout=[0, 1, 1, 1],
            sliding_window_layout=[0, 1, 1, 1], moe_num_primary_experts=4,
            first_expert_held=2, published={"moe_num_primary_experts": 8},
            moe_num_active_primary_experts=3, vocab_size=320,
            sequence_length=64, flash_blocks=[16, 16], flash_interpret=True)

# PowerInfer/SmallThinker-21BA3B-Instruct's config.json as the catalog has it
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


def _real():
    return mf.cell(mf.load(), CELL)["config"]


def _tiny_config(**changes):
    config = copy.deepcopy(_real())
    config.update(copy.deepcopy(TINY))
    config.update(changes)
    return config


def test_the_manifest_with_the_thirteenth_cell_is_sound():
    manifest = mf.load()
    assert mf.check(manifest) == []
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": NAME, "traffic": "ssgd_swa_nope_1chip",
                    "chips": 1}
    for word in ("16,384", "4 early routings", "1 causal core", "3 bands",
                 "44 %", "ReGLU", "24,576", "16 held", "4/52"):
        assert word in cell["why"], word
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry == {**CONFIG, "why": entry["why"]}
    for word in ("routed from the layer's input", "before the mixer", "ReGLU",
                 "top-6-of-64", "no positions", "window-4,096", "28-on-4",
                 "share of 4", "16 experts", "1/8 vocab"):
        assert word in entry["why"], word
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == PER_LAYER
    assert sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", []) and m not in mine) == sorted(JOINED)
    assert len(manifest["configs"]) >= 12 and len(manifest["workloads"]) >= 13
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # additions at the ends: the twelfth cell's entries stand right before these
    at = [w["name"] for w in manifest["workloads"]].index(CELL)
    assert manifest["workloads"][at - 1]["name"] == "keye_vl_2_0_30b_a3b.ssgd_dsa_1chip"
    assert manifest["configs"][at - 2]["name"] == "keye_vl_2_0_30b_a3b"
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[names.index("early_router_ms") - 1] == "dsa_mix_ms"
    for metric in manifest["per_layer"]:
        if metric["name"] in JOINED:
            assert metric["workloads"].index(CELL) >= 1  # behind what was there
        if metric["name"] in ("attn_proj_ms", "full_core_ms", "window_core_ms"):
            assert CELL not in metric["workloads"]  # the cell has its own


def test_the_configuration_is_the_catalogs_but_for_its_cut():
    manifest = mf.load()
    config = _real()
    differs = sorted(k for k, v in CATALOG.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "rope_layout",
        "sliding_window_layout", "vocab_size"]
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 16, 18992)
    assert config["rope_layout"] == config["sliding_window_layout"] == [0, 1, 1, 1]
    assert config["published"] == {k: CATALOG[k] for k in config["reduced"]}
    # one chip's quarter of a 4-chip layer's experts, an eighth of the rows
    assert config["moe_num_primary_experts"] * 4 == CATALOG["moe_num_primary_experts"]
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["source"] == config["source"] and entry["source"].endswith(
        "PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json")
    assert "4 chips share each layer's 64 routed experts" in config["deployment"]
    assert "18,992 of 151,936 rows a chip" in config["deployment"]
    assert "layers 4 to 51" in config["deployment"] and "52.9 %" in config["deployment"]
    # the router's input first among what is assumed
    assert config["assumed"][0].startswith("router_input layer_input")
    assert "llm_build_smallthinker" in config["assumed"][0]
    assert len(config["assumed"]) >= 12
    for word in ("before input_layernorm", "norm_topk_prob", "ReGLU", "j // 7",
                 "no q/k norm", "rope_scaling null", "sliding_window_size",
                 "no auxiliary loss", "routers_trained", "normal(0, 0.02)",
                 "uniform", "3e-4", "recomputed_layer_types", "44 %"):
        assert any(word in line for line in config["assumed"]), word
    assert config["sequence_length"] == 16384 == config["max_position_embeddings"]
    assert config["routers_trained"] is False and config["first_expert_held"] == 0
    assert config["router_input"] == "layer_input"
    traffic = mf.cell(manifest, CELL)["traffic"]
    assert (traffic["per_chip_batch"], traffic["pool"], traffic["mesh"]) == (
        1, 8, {"dp": 1})
    assert (traffic["launcher"], traffic["step"], traffic["placement"]) == (
        "none", "ssgd", "shard_batch")
    assert traffic["optimizer"] == {"name": "adamw_warmup", "learning_rate": 0.0003,
                                    "warmup_steps": 2000}  # since PR 67


def test_the_cut_holds_the_parameters_its_file_says():
    """ISSUE 65's count, by `eval_shape`, against the file's `parameters` and
    `state_bytes`: 20.97 M a mixer, 0.16 M a router, 94.37 M the experts held
    of a layer, 115.51 M a layer."""
    config = _real()
    state = jax.eval_shape(lambda: smallthinker.init(config, 0))

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    full, band = state["layers"]
    assert full["wq"].shape == (1, 2560, 3584) and band["wq"].shape == (3, 2560, 3584)
    assert band["wk"].shape == band["wv"].shape == (3, 2560, 512)
    assert band["router"].shape == (3, 2560, 64)
    assert band["w_gate"].shape == band["w_up"].shape == (3, 16, 2560, 768)
    assert band["w_down"].shape == (3, 16, 768, 2560)
    assert size({k: full[k] for k in ("wq", "wk", "wv", "wo")}) == 20_971_520
    assert size({k: full[k] for k in ("w_gate", "w_up", "w_down")}) == 94_371_840
    assert size(full) == size(band) / 3 == 115_512_320
    assert size(state["embed"]) == size(state["lm_head"]) == 48_619_520
    assert size(state) == config["parameters"] == 559_290_880
    assert 16 * size(state) == config["state_bytes"] == 8_948_654_080
    assert 0.52 < config["state_bytes"] / 16.91e9 < 0.53  # 52.9 % of the chip
    mc = smallthinker.model_config(config)
    assert mc.experts_held == (0, 16) and mc.n_experts == 64 and mc.top_k == 6
    assert all(kind.layer_remat for kind, _ in mc.stacks)


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("norm_topk_prob", False),
    ("moe_primary_router_apply_softmax", False),
    ("rope_scaling", {"rope_type": "yarn"}), ("router_input", "ffn_input"),
    ("rope_layout", [0, 1, 1]), ("sliding_window_layout", [0, 1, 1, 1, 0])])
def test_the_family_refuses_a_layer_it_does_not_run(key, value):
    with pytest.raises(ValueError, match="as published"):
        smallthinker.model_config(_tiny_config(**{key: value}))


# --- operation and byte counts, by hand --------------------------------------

def test_pairs_operations_and_bytes_by_hand():
    real = _real()
    assert smallthinker.seen_pairs(real, 0) == 16384 * 16384 / 2 == 134_217_728
    assert smallthinker.seen_pairs(real, 4096) == (
        16384 * 4096 - 4096 * 4096 / 2) == 58_720_256
    assert 0.43 < 58_720_256 / 134_217_728 < 0.44
    assert smallthinker.core_flops_per_sample(real, 4096) == (
        6 * 2 * 58_720_256 * 28 * 128) == pytest.approx(2.5255e12, rel=1e-4)
    assert smallthinker.core_flops_per_sample(real, 0) == pytest.approx(
        5.7725e12, rel=1e-4)
    assert smallthinker.core_bytes_per_sample(real) == 6 * (28 + 4) * 16384 * 128 * 2
    tiny = _tiny_config()
    assert smallthinker.seen_pairs(tiny, 16) == 64 * 16 - 128
    assert smallthinker.seen_pairs(tiny, 4096) == smallthinker.seen_pairs(tiny, 0)
    assert [(r, w) for r, w, _ in smallthinker.layers_of(real)] == [
        (False, 0), (True, 4096), (True, 4096), (True, 4096)]


def test_flops_per_sample_by_hand():
    """Per token at the tests' size: the mixer's four projections, the router
    over 8 (two passes where it is not trained), 3 x 4 / 8 of a routed expert,
    the head 320 x 64; 2 operations a multiply-add; one causal core and three
    bands; nothing for the rows of the share's chunk that came to no group."""
    config = _tiny_config()
    mixer = 2 * 64 * 64 + 2 * 64 * 32
    assert smallthinker.mixer_params_per_token(config) == mixer
    assert smallthinker.expected_expert_passes(config) == 1.5
    experts = 1.5 * 3 * 64 * 32
    cores = 6 * 2 * 4 * 16 * (2048 + 3 * 896)
    assert config["routers_trained"] is False
    per_token = 3 * 320 * 64 + 4 * (3 * (mixer + experts) + 2 * 64 * 8)
    assert smallthinker.flops_per_sample(config) == 2 * per_token * 64 + cores
    assert smallthinker.flops_per_sample({**config, "routers_trained": True}) == (
        2 * (per_token + 4 * 64 * 8) * 64 + cores)
    real = _real()
    assert smallthinker.expected_expert_passes(real) == 1.5  # of a token's 6
    # a causal core 5.77 TFLOP, three bands 2.53 each, the matmuls 16.5
    assert smallthinker.flops_per_sample(real) == pytest.approx(29.90e12, rel=1e-3)


def test_the_multiplying_parameters_are_the_initialised_trees():
    """Every matrix of the initialised tree multiplies every token once, but
    the held experts (a token takes 6 x 16 / 64 of one on average) and the
    embedding (a lookup)."""
    real = _real()
    state = jax.eval_shape(lambda: smallthinker.init(real, 0))
    full = state["layers"][0]
    size = lambda *names: sum(full[n].size for n in names)
    assert smallthinker.mixer_params_per_token(real) == size("wq", "wk", "wv", "wo")
    assert smallthinker.router_params_per_token(real) == size("router")
    assert smallthinker.expert_params_per_token(real) * 16 / 1.5 == size(
        "w_gate", "w_up", "w_down")


def test_host_batches_come_from_the_seed_uniform_over_the_slice():
    config = _tiny_config(sequence_length=4096)
    a = smallthinker.host_batch(config, 2**31 + 11, 3, 2)
    b = smallthinker.host_batch(config, 2**31 + 11, 3, 2)
    c = smallthinker.host_batch(config, 2**31 + 12, 3, 2)
    assert a.shape == (2, 4097) and a.dtype == np.int32  # S + 1 ids
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < 320
    counts = np.bincount(a.ravel(), minlength=320)
    assert counts.min() > 5 and counts.max() < 64 and 150 < np.median(a) < 170
    real = smallthinker.host_batch(_real(), 2**31 + 11, 0, 1)
    assert real.shape == (1, 16385) and 18900 < real.max() < 18992


# --- the program against the reference --------------------------------------

def test_the_reference_imports_nothing_of_the_program_or_of_another_reference():
    import benchmark.reference.smallthinker as reference

    with open(reference.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports
                            if "kungfu_tpu" in line or "benchmark" in line]
    assert "pallas" not in text and "custom_vjp" not in text
    assert 'default_matmul_precision("highest")' in text and "lax.top_k" in text
    assert "before `input_layernorm`" in text  # the departure, in the file's head
    assert smallthinker.REFERENCE_SAMPLES == 1


def test_the_real_program_holds_to_its_declared_precision():
    """At ISSUE 65's sizes, traced and not run: the state float32, the loss
    and every product over the 18,992 rows of the head float32; a bfloat16
    head is caught."""
    config = _real()
    assert smallthinker.head_width(config) == 18992 != config["sequence_length"]
    state = jax.eval_shape(lambda: smallthinker.init(config, 0))
    sample = smallthinker.host_batch(config, 0, 0, smallthinker.REFERENCE_SAMPLES)
    traced = smallthinker.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, 18992, traced.jaxpr, state, state) == []
    low = {**config, "head_dtype": "bfloat16"}
    faults = harness.precision_faults(low, 18992, traced.jaxpr, state, state)
    assert faults and all("float32" in fault for fault in faults)
    low = {**config, "param_dtype": "bfloat16"}
    assert harness.precision_faults(low, 18992, traced.jaxpr, state, state)


# --- the readers on a drawn trace ---------------------------------------

MS = 8_000_000  # a unit of the drawing below, in ns: 8 ms
# Two steps of 60 units on one chip, each alike (forward: a full layer and a
# window layer, the head; then a window layer run again and its backward pass,
# the optimizer, a stray op):
STEP_OPS = [("embed", 0, 0.5), ("router", 0.5, 1), ("sort", 1, 1.5),
            ("qkv.full", 1.5, 2.5), ("core.full", 2.5, 5.5), ("wo.full", 5.5, 6),
            ("gather", 6, 7), ("gmm.fwd", 7, 9), ("scatter", 9, 10),
            ("router.w", 10, 10.5), ("sort.w", 10.5, 11), ("qkv.w", 11, 12),
            ("rope", 12, 12.5), ("core.w", 12.5, 14.5), ("wo.w", 14.5, 15),
            ("gmm.fwd.w", 15, 17), ("aux", 17, 17.5),
            ("head", 17.5, 20), ("head.bwd", 20, 23),
            ("router.again", 23, 23.5), ("count.again", 23.5, 23.75),
            ("qkv.again", 23.75, 24.75), ("gmm.bwd", 24.75, 28.75),
            ("gather.bwd", 28.75, 30.75), ("router.bwd", 30.75, 31.25),
            ("core.w.dq", 31.25, 33.25), ("core.w.dkv", 33.25, 36.25),
            ("rope.bwd", 36.25, 36.75), ("qkv.bwd", 36.75, 38.75),
            ("core.full.dq", 38.75, 41.75), ("core.full.dkv", 41.75, 45.75),
            ("adamw", 45.75, 48.75), ("stray", 48.75, 49.25)]
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
CHUNK = "moe/while/body/closed_call"
SCOPES = {
    "embed": "jit(local_step)/jvp(embed)/gather",
    "router": f"{FWD}/moe/moe_early_router/dot_general",
    "sort": f"{FWD}/moe/moe_plan/jit(argsort)/sort",
    "qkv.full": f"{FWD}/attn/dot_general",
    "core.full": f"{FWD}/attn/attn_full/attn_core/pallas_call",
    "wo.full": f"{FWD}/attn/dot_general",
    "gather": f"{FWD}/{CHUNK}/moe_dispatch/gather",
    "gmm.fwd": "ragged-dot-none",
    "scatter": f"{FWD}/{CHUNK}/moe_combine/scatter-add",
    "router.w": f"{FWD}/moe/moe_early_router/top_k",
    "sort.w": f"{FWD}/moe/moe_plan/jit(argsort)/sort",
    "qkv.w": f"{FWD}/attn/dot_general",
    "rope": f"{FWD}/attn/rope/jit(_turned)/pallas_call",
    "core.w": f"{FWD}/attn/attn_window/attn_core/pallas_call",
    "wo.w": f"{FWD}/attn/dot_general",
    "gmm.fwd.w": "ragged-dot-none",
    "aux": f"{FWD}/moe/moe_router/reduce_sum",
    "head": "jit(local_step)/jvp(head_loss)/dot_general",
    "head.bwd": "jit(local_step)/transpose(jvp(head_loss))/dot_general",
    "router.again": f"{AGAIN}/moe/moe_early_router/dot_general",
    "count.again": f"{AGAIN}/moe/moe_plan/reduce_sum",
    "qkv.again": f"{AGAIN}/attn/dot_general",
    "gmm.bwd": "ragged-dot-none",
    "gather.bwd": f"{BWD}/{CHUNK}/transpose(jvp(moe_dispatch))/scatter-add",
    "router.bwd": f"{BWD}/moe/moe_early_router/dot_general",
    "core.w.dq": f"{BWD}/attn/attn_window/attn_core/pallas_call",
    "core.w.dkv": f"{BWD}/attn/attn_window/attn_core/pallas_call",
    "rope.bwd": f"{BWD}/attn/rope/jit(_turned)/pallas_call",
    "qkv.bwd": f"{BWD}/attn/dot_general",
    "core.full.dq": f"{BWD}/attn/attn_full/attn_core/pallas_call",
    "core.full.dkv": f"{BWD}/attn/attn_full/attn_core/pallas_call",
    "adamw": "jit(local_step)/optimizer/optimizer_update/add",
}
READERS = (early_router_ms, swa_core_ms, swa_core_roofline_pct,
           nope_full_core_ms, nope_full_core_roofline_pct, swa_attn_proj_ms,
           reglu_moe_ms)


def _record(samples_per_step=1):
    return {"workload": CELL, "scopes": SCOPES, "samples_per_step": samples_per_step,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_drawn_times():
    record = _record()
    # the routers' products and top-k each way and again, the sorts once, the
    # count again
    assert early_router_ms.read(record, DRAWN) == pytest.approx(
        8 * (0.5 + 0.5 + 0.5 + 0.5 + 0.5 + 0.25 + 0.5))
    assert swa_core_ms.read(record, DRAWN) == pytest.approx(8 * (2 + 2 + 3))
    assert nope_full_core_ms.read(record, DRAWN) == pytest.approx(8 * (3 + 3 + 4))
    # `attn` less the two: the projections and the rotary pass, each way, again
    assert swa_attn_proj_ms.read(record, DRAWN) == pytest.approx(
        8 * (1 + 0.5 + 1 + 0.5 + 0.5 + 1 + 0.5 + 2))
    # everything under `moe`, the routing ahead of the mixer in it, and the
    # grouped matmuls by their name
    assert reglu_moe_ms.read(record, DRAWN) == pytest.approx(
        8 * (2.75 + 1 + 1 + 0.5 + 0.5 + 2 + 2 + 2 + 4))


def test_drawn_shares_of_the_rooflines():
    """At the real widths: one sequence of 16,384 tokens a step, one full
    layer and three window layers, both kinds bound by their operations."""
    record = _record()
    peaks = harness.load_peaks("TPU v5 lite")
    band = 3 * 12 * 58_720_256 * 28 * 128 / peaks["bf16_flops"]
    assert band > 3 * smallthinker.core_bytes_per_sample(_real()) / peaks["hbm_bytes_per_s"]
    assert swa_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * band / 56e-3)
    full = 12 * 134_217_728 * 28 * 128 / peaks["bf16_flops"]
    assert nope_full_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * full / 80e-3)
    for reader in (swa_core_roofline_pct, nope_full_core_roofline_pct):
        assert reader.read(_record(2), DRAWN) == pytest.approx(
            2 * reader.read(record, DRAWN))
        assert 0 < reader.read(record, DRAWN) < 100
    # a record of another family's cell has no such layers to count
    other = {**record, "workload": "laguna_s_2_1.ssgd_1seq_1chip"}
    assert swa_core_roofline_pct.read(other, DRAWN) is None


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
              "program_memory": {"total_bytes": 16_930_000_000},
              "memory_stats_peak_bytes": 1, "correct": True, "attempted": 20,
              "failed": 0}
    line = end_to_end.result_line(record, DRAWN, manifest)
    mine = {x["name"] for x in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(line["metrics"]) == mine
    assert {r.__name__.split(".")[-1] for r in READERS} <= mine
    assert set(JOINED) <= mine
    assert not {"attn_proj_ms", "full_core_ms", "window_core_ms", "flash_core_ms",
                "moe_sigmoid_ms", "dsa_core_ms"} & mine
    value = lambda name: line["metrics"][name]["value"]
    # the accepted readers the cell joins, on this cell's scopes
    assert value("optimizer_ms") == pytest.approx(8 * 3.0)
    assert value("head_loss_ms") == pytest.approx(8 * (2.5 + 3))
    assert value("moe_ms") == value("reglu_moe_ms")
    assert value("expert_ffn_ms") == pytest.approx(8 * (2 + 2 + 4))
    # dispatch, combine and the losses' counters; the routing has its own
    assert value("moe_dispatch_ms") == pytest.approx(8 * (1 + 1 + 0.5 + 2))
    # the seven that claim device time leave the embedding and the stray op
    step = sum(b - a for _, a, b in STEP_OPS)
    claimed = sum(value(name) for name in (
        "swa_core_ms", "nope_full_core_ms", "swa_attn_proj_ms", "reglu_moe_ms",
        "head_loss_ms", "optimizer_ms"))
    assert 8 * step - claimed == pytest.approx(8 * (0.5 + 0.5))
    assert line["metrics"]["swa_core_roofline_pct"]["unit"] == "%"
    assert mf.check_result_line(line, manifest, CELL, traced=True) == []
