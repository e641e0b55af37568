"""The GLM-4-MoE-Lite family, its configuration and its five readers (PR 41):
the whole of `harness.measure` at tiny size on the CPU mesh, the parameter,
operation and byte counts against the initialised tree and sums made by hand,
the batches, the readers against a drawn trace, and the configuration file
against the catalog's numbers.

The cell is the manifest's seventh, under ISSUE 41's traffic (AdamW at a
constant 3e-4); these tests find it and its entries by name, wherever later
cells put them."""

import copy
import json
import time

import jax
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.families import glm4_moe_lite
from benchmark.launchers.none import OneProcess
from benchmark.layer_metrics import (flash_core_ms, mla_core_ms,
                                     mla_core_roofline_pct, mla_proj_ms,
                                     moe_ms, moe_sigmoid_ms, mtp_ms)
from drawn_setup import child_marks, drawn_setup

CELL = "glm_4_7_flash.ssgd_mtp_8k_1chip"
# the five metrics the cell brought, and the older lists it joined: the step's
# parts, which tests/benchmark/test_bench_setup.py wants of every transformer
# cell. `flash_core_ms` and `moe_ms` read its step as they stand, and
# tests/benchmark/test_bench_olmoe.py holds their lists to the OLMoE cell
# alone: `mla_core_ms` and `moe_sigmoid_ms` are their readers under this
# cell's names
MINE = (("mla_core_ms", "ms", "lower", "Kernels"),
        ("mla_core_roofline_pct", "%", "higher", "Kernels"),
        ("mla_proj_ms", "ms", "lower", "Model"),
        ("mtp_ms", "ms", "lower", "Model"),
        ("moe_sigmoid_ms", "ms", "lower", "Model"))
JOINED = ("optimizer_ms", "head_loss_ms")

# every mechanism on, at the tests' size (tests/test_glm_4_7_flash.py); the
# kernel in interpret mode by a key of the configuration
TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24,
            qk_rope_head_dim=8, v_head_dim=32, n_routed_experts=4,
            first_expert_held=4, published={"n_routed_experts": 16},
            vocab_size=320, sequence_length=64, flash_blocks=[32, 32],
            flash_interpret=True)  # 320: no layer's width

# zai-org/GLM-4.7-Flash's config.json as the catalog has it
CATALOG = {"attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 10240, "max_position_embeddings": 202752,
           "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
           "topk_method": "noaux_tc", "norm_topk_prob": True,
           "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
           "n_routed_experts": 64, "n_shared_experts": 1,
           "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
           "first_k_dense_replace": 1, "num_hidden_layers": 47,
           "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
           "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
           "rope_scaling": None, "rope_theta": 1000000,
           "tie_word_embeddings": False, "q_lora_rank": 768,
           "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
           "v_head_dim": 256, "vocab_size": 154880}


def _real():
    return mf.cell(mf.load(), CELL)["config"]


def _tiny_config(**changes):
    config = copy.deepcopy(_real())
    config.update(TINY)
    config.update(changes)
    return config


def test_the_manifest_with_the_seventh_cell_is_sound():
    manifest = mf.load()
    assert mf.check(manifest) == []
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "glm_4_7_flash",
                    "traffic": "ssgd_mtp_8k_1chip", "chips": 1}
    (entry,) = [c for c in manifest["configs"] if c["name"] == "glm_4_7_flash"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == [
        {"name": name, "unit": unit, "better": better, "source": "device_trace",
         "layer": layer, "moves": "step_ms_p50", "workloads": [CELL]}
        for name, unit, better, layer in MINE]
    at = manifest["per_layer"].index(mine[0])
    assert manifest["per_layer"][at:at + 5] == mine  # the five it came with
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", []) and m not in mine) == sorted(JOINED)


def test_the_configuration_is_the_catalogs_but_for_its_cut():
    manifest = mf.load()
    config = _real()
    differs = sorted(k for k, v in CATALOG.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 19360)
    assert config["published"] == {k: CATALOG[k] for k in config["reduced"]}
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["n_routed_experts"] * 8 == CATALOG["n_routed_experts"]
    assert glm4_moe_lite.layer_types(config) == ["dense"] + ["sparse"] * 4
    (entry,) = [c for c in manifest["configs"] if c["name"] == "glm_4_7_flash"]
    assert entry["source"] == config["source"] and entry["source"].endswith(
        "zai-org/GLM-4.7-Flash/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert "8 chips" in config["deployment"] and len(config["assumed"]) >= 10
    for word in ("multi-token", "rotate-half", "bias", "0.3", "normal(0, 0.02)",
                 "uniform", "recomputed"):
        assert any(word in line for line in config["assumed"]), word
    assert config["sequence_length"] == 8192 and config["mtp_loss_weight"] == 0.3
    assert config["routers_trained"] is False
    assert config["flash_blocks"] == [512, 512]
    traffic = mf.cell(manifest, CELL)["traffic"]
    assert (traffic["per_chip_batch"], traffic["pool"], traffic["mesh"]) == (
        1, 8, {"dp": 1})
    assert (traffic["launcher"], traffic["step"], traffic["placement"]) == (
        "none", "ssgd", "shard_batch")
    # ISSUE 41's: a constant rate from the initial parameters
    assert traffic["optimizer"] == {"name": "adamw", "learning_rate": 0.0003}


def test_the_cut_holds_706_5_million_parameters():
    """ISSUE 41's count, by `eval_shape`: 21.76 M in a mixer, 84.67 M in layer
    0, 106.82 M in an expert layer (75.50 M held experts, 9.44 M shared, 0.13
    M router), 2 x 39.65 M in embedding and head, 115.2 M in the
    multi-token-prediction module; 11.30e9 bytes at 16 a parameter."""
    state = jax.eval_shape(lambda: glm4_moe_lite.init(_real(), 0))

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    dense, sparse = state["layers"]
    mixer = {k: v for k, v in dense.items()
             if k.startswith(("w_q_", "w_kv_", "q_latent", "kv_latent")) or k == "wo"}
    assert size(mixer) == 21_759_232 == pytest.approx(21.76e6, rel=1e-4)
    assert size(dense) == pytest.approx(84.67e6, rel=2e-4)
    assert size(sparse) / 4 == pytest.approx(106.82e6, rel=1e-4)
    assert size({k: sparse[k] for k in ("w_gate", "w_up", "w_down")}) / 4 == (
        8 * 3 * 2048 * 1536) == pytest.approx(75.50e6, rel=1e-4)
    assert sparse["router"].shape == (4, 2048, 64)
    assert sparse["router_bias"].shape == (4, 64)
    assert size(state["embed"]) == size(state["lm_head"]) == 19360 * 2048
    assert size(state["mtp"]) == pytest.approx(115.21e6, rel=2e-4)
    assert size(state["mtp"]) == size(sparse) // 4 + 2 * 2048 * 2048 + 3 * 2048
    assert size(state) == 706_518_848 == pytest.approx(706.5e6, rel=1e-3)
    assert 11.30e9 < 16 * size(state) < 11.31e9
    mc = glm4_moe_lite.model_config(_real())
    assert [(k.mixer, k.ffn, k.layer_remat, n) for k, n in mc.stacks] == [
        ("latent", "swiglu", False, 1), ("latent", "moe", True, 4)]
    assert mc.experts_held == (0, 8) and mc.n_experts == 64 and mc.top_k == 4
    assert mc.latent_dims == (768, 512, 192, 64, 256) and mc.n_heads == 20
    assert (mc.router_scores, mc.router_bias, mc.routed_scale) == ("sigmoid", True, 1.8)
    assert (mc.mtp_depth, mc.mtp_weight, mc.rope_theta) == (1, 0.3, 1e6)


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("norm_topk_prob", False), ("rope_scaling", {"type": "yarn"}),
    ("topk_method", "greedy"), ("n_group", 8), ("n_shared_experts", 2),
    ("num_nextn_predict_layers", 0), ("num_key_value_heads", 2),
    ("partial_rotary_factor", 0.5), ("first_k_dense_replace", 0)])
def test_the_family_refuses_a_layer_it_does_not_run(key, value):
    with pytest.raises(ValueError, match="as published"):
        glm4_moe_lite.model_config(_tiny_config(**{key: value}))


# --- operation and byte counts, by hand --------------------------------------

def test_core_operations_and_bytes_by_hand():
    """One sequence of 64 tokens, 4 heads of 24 + 8 q/k and 32 value
    features: 64 x 64 / 2 pairs a head; QK^T and its two transposes over 32
    features, PV and its two over 32; 2 operations a multiply-add."""
    config = _tiny_config()
    pairs = 64 * 64 / 2
    assert glm4_moe_lite.core_flops_per_sample(config) == (
        3 * 2 * pairs * 4 * (32 + 32))
    # q, k, dq, dk (and q, k read again) at 32; v, o, do, dv (v, o again) at 32
    assert glm4_moe_lite.core_bytes_per_sample(config) == 6 * (32 + 32) * 4 * 64 * 2
    real = _real()
    # the issue's arithmetic: 2.06 T a core, 6 cores a step
    assert glm4_moe_lite.core_flops_per_sample(real) == pytest.approx(2.0616e12, rel=1e-4)
    assert glm4_moe_lite.core_bytes_per_sample(real) == 6 * 512 * 20 * 8192 * 2
    assert len(glm4_moe_lite.blocks(real)) == 6
    # on the v5e the operations bound the core, 8.5 times the bytes' time
    peaks = harness.load_peaks("TPU v5 lite")
    t_flops = glm4_moe_lite.core_flops_per_sample(real) / peaks["bf16_flops"]
    t_bytes = glm4_moe_lite.core_bytes_per_sample(real) / peaks["hbm_bytes_per_s"]
    assert t_flops / t_bytes == pytest.approx(8.51, rel=1e-2)


def test_flops_per_sample_by_hand():
    """Per token: a mixer (W_q_down 64 x 24, W_q_up 24 x 128, W_kv_down 64 x
    24, W_kv_up 16 x 224, W_o 128 x 64) in each of the three layers and the
    module's block; the dense feed-forward 3 x 64 x 128; in the two expert
    layers and the module's the router over 16, the shared expert and 4 x 4 /
    16 of a routed expert; the module's projection 128 x 64; the head 320 x
    64 twice; 2 operations a multiply-add, x 3 for forward and backward, but
    x 2 for a router that is not trained, as the cell's are; the cores."""
    config = _tiny_config()
    mixer = 64 * 24 + 24 * 128 + 64 * 24 + 16 * 224 + 128 * 64
    assert glm4_moe_lite.mixer_params_per_token(config) == mixer
    expert = 3 * 64 * 32
    sparse = 64 * 16 + expert + 1.0 * expert
    params = 2 * 320 * 64 + 128 * 64 + 4 * mixer + 3 * 64 * 128 + 3 * sparse
    assert glm4_moe_lite.expected_expert_passes(config) == 1.0
    assert glm4_moe_lite.matmul_params_per_token(config) == params
    cores = 4 * (3 * 2 * 2048 * 4 * 64)
    assert config["routers_trained"] is False
    assert glm4_moe_lite.flops_per_sample(config) == (
        3 * 2 * params * 64 - 3 * 2 * 64 * 16 * 64 + cores)
    assert glm4_moe_lite.flops_per_sample({**config, "routers_trained": True}) == (
        3 * 2 * params * 64 + cores)
    real = _real()
    assert glm4_moe_lite.expected_expert_passes(real) == 0.5
    # the issue's arithmetic: 352.6 M multiplying parameters a token, 29.7 T
    # a step of which 17.3 T outside the cores
    assert glm4_moe_lite.matmul_params_per_token(real) == pytest.approx(352.6e6, rel=1e-3)
    assert glm4_moe_lite.flops_per_sample(real) == pytest.approx(29.69e12, rel=1e-3)
    assert glm4_moe_lite.flops_per_sample(real) - 6 * 2.0616e12 == pytest.approx(
        17.32e12, rel=1e-3)


def test_the_multiplying_parameters_are_the_initialised_trees():
    """Every matrix of the initialised tree multiplies every token once, but
    the embedding (a lookup), the head (twice) and the held experts (a token
    takes 4 x 8 / 64 of one on average): the family's count from the
    configuration against the tree's own leaves."""
    real = _real()
    state = jax.eval_shape(lambda: glm4_moe_lite.init(real, 0))
    # every leaf but the norms' scales and the biases is a matrix
    matrices = sum(
        x.size for path, x in jax.tree_util.tree_leaves_with_path(state)
        if not jax.tree_util.keystr(path).rstrip("']").endswith(
            ("_scale", "_norm", "router_bias")))
    experts = 5 * 8 * 3 * 2048 * 1536
    want = (matrices - state["embed"].size + state["lm_head"].size
            - experts + 5 * 0.5 * 3 * 2048 * 1536)
    assert glm4_moe_lite.matmul_params_per_token(real) == want


def test_host_batches_come_from_the_seed_uniform_over_the_slice():
    config = _tiny_config(sequence_length=4096)
    a = glm4_moe_lite.host_batch(config, 2**31 + 11, 3, 2)
    b = glm4_moe_lite.host_batch(config, 2**31 + 11, 3, 2)
    c = glm4_moe_lite.host_batch(config, 2**31 + 12, 3, 2)
    assert a.shape == (2, 4098) and a.dtype == np.int32  # S + 2 ids
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < 320
    counts = np.bincount(a.ravel(), minlength=320)
    assert counts.min() > 5 and counts.max() < 64 and 150 < np.median(a) < 170
    real = glm4_moe_lite.host_batch(_real(), 2**31 + 11, 0, 1)
    assert real.shape == (1, 8194) and real.max() < 19360
    assert np.bincount(real.ravel(), minlength=19360).max() < 10


# --- the program against the reference --------------------------------------

def _both(dtype, seed=5):
    config = _tiny_config(compute_dtype=dtype)
    state = glm4_moe_lite.init(config, seed)
    sample = glm4_moe_lite.host_batch(config, seed, 0, 2)
    got = glm4_moe_lite.program_loss_and_grads(config)(state, sample)
    want = glm4_moe_lite.reference_loss_and_grads(config, state, sample)
    return config, state, sample, got, want


def test_reference_equals_program_in_float32():
    config, state, sample, (loss, grads), (ref_loss, ref_grads) = _both("float32")
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    assert harness.relative_error(grads, ref_grads) <= 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    assert glm4_moe_lite.differing_choices(config, state, sample) == 0
    stats = glm4_moe_lite.routing_stats(config, state, sample)
    assert stats["dropped"] == [0, 0, 0] and stats["layer"] == [1, 2, 3]
    assert stats["held_rows"] == np.sum(stats["counts"], axis=1).tolist()
    assert len(stats["bias_moved"]) == 3
    # the routers' and the biases' gradients are zero in both (the cell)
    for tree in (grads, ref_grads):
        for layer in (tree["layers"][1], tree["mtp"]["layer"]):
            assert not np.asarray(layer["router"]).any()
            assert not np.asarray(layer["router_bias"]).any()
    losses = glm4_moe_lite.program_losses(config, state, sample)
    assert float(loss) == pytest.approx(losses["main"] + 0.3 * losses["mtp"], rel=1e-6)


def test_bfloat16_program_is_within_the_familys_tolerances():
    _, _, _, (loss, grads), (ref_loss, ref_grads) = _both("bfloat16")
    assert abs(float(loss) - float(ref_loss)) <= (
        glm4_moe_lite.LOSS_RTOL * abs(float(ref_loss)))
    error = harness.relative_error(grads, ref_grads)
    assert 1e-4 < error <= glm4_moe_lite.GRAD_RTOL, error


def test_the_reference_computes_in_blocks_what_it_computes_at_once():
    config = _tiny_config(compute_dtype="float32")
    state = glm4_moe_lite.init(config, 3)
    sample = glm4_moe_lite.host_batch(config, 3, 0, 1)
    from benchmark.reference import glm_4_7_flash as reference

    whole = dict(glm4_moe_lite._hyper(config), query_block=64)
    at_once = reference.loss_and_grads(state, sample, **whole)
    in_blocks = reference.loss_and_grads(state, sample, **{**whole, "query_block": 16})
    assert float(at_once[0]) == pytest.approx(float(in_blocks[0]), rel=1e-6)
    assert harness.relative_error(in_blocks[1], at_once[1]) <= 1e-5


def test_the_reference_imports_nothing_of_the_program():
    import benchmark.reference.glm_4_7_flash as reference

    with open(reference.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "kungfu_tpu" in line]
    assert "pallas" not in text and "custom_vjp" not in text
    assert 'default_matmul_precision("highest")' in text
    assert glm4_moe_lite.REFERENCE_SAMPLES == 1


def test_the_cells_program_holds_to_its_declared_precision():
    config = _tiny_config()
    state = jax.eval_shape(lambda: glm4_moe_lite.init(config, 0))
    sample = glm4_moe_lite.host_batch(config, 0, 0, glm4_moe_lite.REFERENCE_SAMPLES)
    traced = glm4_moe_lite.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, glm4_moe_lite.head_width(config),
                                    traced.jaxpr, state, state) == []
    low = _tiny_config(param_dtype="bfloat16")
    assert harness.precision_faults(low, glm4_moe_lite.head_width(low),
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
    assert record["flops_per_sample"] == glm4_moe_lite.flops_per_sample(cell["config"])
    json.dumps(record)
    assert all(v > 0 for v in end_to_end.values(record).values())
    with pytest.raises(RuntimeError, match="chip runs only"):
        end_to_end.result_line(record, None, m)


# --- the readers on a drawn trace ---------------------------------------

MS = 8_000_000  # a unit of the drawing below, in ns: 8 ms
# Two steps of 60 units on one chip, each alike:
#   down [0, 1)  latent.norm [1, 1.5)  up [1.5, 4)  rope [4, 5)
#   core.fwd [5, 9)  wo [9, 10)  router [10, 11)  gmm.fwd [11, 13)
#   shared.fwd [13, 14)  head [14, 17)  mtp.proj [17, 18)  mtp.up [18, 19)
#   mtp.core.fwd [19, 20)  mtp.router [20, 20.5)  mtp.head [20.5, 23.5)
#   mtp.head.bwd [23.5, 29)  mtp.core.bwd [29, 31.5)  mtp.up.bwd [31.5, 33)
#   core.bwd [33, 43)  up.bwd [43, 47)  gmm.bwd [47, 51)  shared.bwd [51, 53)
#   adamw [53, 56) (under `optimizer`)
STEP_OPS = [("down", 0, 1), ("latent.norm", 1, 1.5), ("up", 1.5, 4),
            ("rope", 4, 5), ("core.fwd", 5, 9), ("wo", 9, 10),
            ("router", 10, 11), ("gmm.fwd", 11, 13), ("shared.fwd", 13, 14),
            ("head", 14, 17), ("mtp.proj", 17, 18), ("mtp.up", 18, 19),
            ("mtp.core.fwd", 19, 20), ("mtp.router", 20, 20.5),
            ("mtp.head", 20.5, 23.5), ("mtp.head.bwd", 23.5, 29),
            ("mtp.core.bwd", 29, 31.5), ("mtp.up.bwd", 31.5, 33),
            ("core.bwd", 33, 43), ("up.bwd", 43, 47), ("gmm.bwd", 47, 51),
            ("shared.bwd", 51, 53), ("adamw", 53, 56)]
DRAWN = {
    "chips": [{"plane": "/device:TPU:0", "program": "jit_step",
               "steps": [[0, 60 * MS], [60 * MS, 120 * MS]],
               "ops": [[name, int((at + a) * MS), int((at + b) * MS)]
                       for at in (0, 60) for name, a, b in STEP_OPS]}],
    "host": [], "lines": {},
}
FWD = "jit(step)/shard_map/jvp()/while/body/closed_call"
BWD = "jit(step)/shard_map/transpose(jvp())/while/body/closed_call/checkpoint"
MTP = "jit(step)/shard_map/jvp(mtp)"
MTP_BWD = "jit(step)/shard_map/transpose(jvp(mtp))"
SCOPES = {
    "down": f"{FWD}/attn/mla_down/dot_general",
    "latent.norm": f"{FWD}/attn/mla_norm/checkpoint/rsqrt",
    "up": f"{FWD}/attn/mla_up/dot_general",
    "rope": f"{FWD}/attn/rope/pallas_call",
    "core.fwd": f"{FWD}/attn/attn_latent/attn_core/pallas_call",
    "wo": f"{FWD}/attn/dot_general",
    "router": f"{FWD}/moe/moe_router/dot_general",
    "gmm.fwd": "ragged-dot-none",
    "shared.fwd": f"{FWD}/moe/moe_shared/dot_general",
    "head": "jit(step)/shard_map/jvp(head_loss)/dot_general",
    "mtp.proj": f"{MTP}/mtp_proj/dot_general",
    "mtp.up": f"{MTP}/checkpoint/attn/mla_up/dot_general",
    "mtp.core.fwd": f"{MTP}/checkpoint/attn/attn_latent/attn_core/pallas_call",
    "mtp.router": f"{MTP}/checkpoint/moe/moe_router/dot_general",
    "mtp.head": f"{MTP}/head_loss/dot_general",
    "mtp.head.bwd": f"{MTP_BWD}/head_loss/dot_general",
    "mtp.core.bwd": f"{MTP_BWD}/checkpoint/attn/attn_latent/attn_core/pallas_call",
    "mtp.up.bwd": f"{MTP_BWD}/checkpoint/attn/mla_up/dot_general",
    "core.bwd": f"{BWD}/attn/attn_latent/attn_core/pallas_call",
    "up.bwd": f"{BWD}/attn/mla_up/dot_general",
    "gmm.bwd": "ragged-dot-none",
    "shared.bwd": f"{BWD}/moe/moe_shared/dot_general",
    "adamw": "jit(step)/shard_map/optimizer/optimizer_update/add",
}


def _record(samples_per_step=1):
    return {"workload": CELL, "scopes": SCOPES, "samples_per_step": samples_per_step,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_drawn_times():
    record = _record()
    # every block's core, the module's among them
    assert mla_core_ms.read(record, DRAWN) == pytest.approx(8 * (4 + 1 + 2.5 + 10))
    assert mla_core_ms.read(record, DRAWN) == flash_core_ms.read(record, DRAWN)
    # `attn` less the cores: projections, norms, the rotary pass, W_o
    assert mla_proj_ms.read(record, DRAWN) == pytest.approx(
        8 * (1 + 0.5 + 2.5 + 1 + 1 + 1 + 1.5 + 4))
    # all of `mtp`, its head pass and its block's core among it; not the
    # grouped-matmul kernels, which carry no scope
    assert mtp_ms.read(record, DRAWN) == pytest.approx(
        8 * (1 + 1 + 1 + 0.5 + 3 + 5.5 + 2.5 + 1.5))
    # `moe` with the kernels claimed by name, the module's router among it
    assert moe_sigmoid_ms.read(record, DRAWN) == pytest.approx(
        8 * (1 + 2 + 1 + 0.5 + 4 + 2))
    assert moe_sigmoid_ms.read(record, DRAWN) == moe_ms.read(record, DRAWN)


def test_drawn_share_of_the_roofline():
    """At the real widths: one sequence of 8,192 tokens a step, six cores
    bound by their operations."""
    record = _record()
    peaks = harness.load_peaks("TPU v5 lite")
    cores = 6 * 3 * 2 * (8192 * 8192 / 2) * 20 * 512 / peaks["bf16_flops"]
    assert cores > 6 * 6 * 512 * 20 * 8192 * 2 / peaks["hbm_bytes_per_s"]
    assert mla_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * cores / 140e-3)
    # a step of two sequences has twice the work in the same drawn time
    assert mla_core_roofline_pct.read(_record(2), DRAWN) == pytest.approx(
        2 * mla_core_roofline_pct.read(record, DRAWN))
    assert 40 < mla_core_roofline_pct.read(record, DRAWN) < 50


def test_the_roofline_counts_nothing_a_core_might_skip():
    """The causal half, each of the six products once, every array once each
    way: the two-pass backward's recomputation is not in it."""
    real = _real()
    assert glm4_moe_lite.core_flops_per_sample(real) == (
        6 * 2 * 8192 * 8192 / 2 * 20 * 256)


READERS = (mla_core_ms, mla_core_roofline_pct, mla_proj_ms, mtp_ms, moe_sigmoid_ms)


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
              "program_memory": {"total_bytes": 16_100_000_000},
              "memory_stats_peak_bytes": 1, "correct": True, "attempted": 20,
              "failed": 0}
    line = end_to_end.result_line(record, DRAWN, manifest)
    mine = {x["name"] for x in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(line["metrics"]) == mine
    assert {r.__name__.split(".")[-1] for r in READERS} <= mine
    assert set(JOINED) <= mine
    # `flash_roofline_pct` counts a core a layer at the OLMoE family's sizes
    assert not {"full_core_ms", "moe_share_ms", "flash_roofline_pct",
                "flash_core_ms", "moe_ms",
                "gattn_core_ms", "moe_held_ms"} & mine
    # the head's reader sees both passes, the module's under `mtp`
    assert line["metrics"]["optimizer_ms"]["value"] == pytest.approx(8 * 3.0)
    assert line["metrics"]["head_loss_ms"]["value"] == pytest.approx(8 * (3 + 3 + 5.5))
    assert line["metrics"]["mtp_ms"]["value"] == pytest.approx(8 * 16.0)
    assert line["metrics"]["mla_core_roofline_pct"]["unit"] == "%"
    assert mf.check_result_line(line, manifest, CELL, traced=True) == []
