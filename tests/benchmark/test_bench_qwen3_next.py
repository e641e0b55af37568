"""The Qwen3-Next family, its cell and its six per-layer metrics (PR 36): the
manifest with the sixth cell, the whole of `harness.measure` on it at tiny
size on the CPU mesh, the parameter, operation and byte counts against sums
made by hand, the batches, the readers against a drawn trace, and the
configuration file against the catalog's numbers."""

import copy
import json
import time

import jax
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.families import qwen3_next
from benchmark.launchers.none import OneProcess
from drawn_setup import child_marks, drawn_setup
from benchmark.layer_metrics import (gattn_core_ms, gattn_core_roofline_pct,
                                     gdn_core_ms, gdn_core_roofline_pct,
                                     gdn_mix_ms, moe_held_ms)

CELL = "qwen3_next_80b_a3b.ssgd_longseq_1chip"
# every mechanism on, at the tests' size (tests/test_qwen3_next.py); the
# kernel in interpret mode by a key of the configuration
TINY = dict(hidden_size=64, head_dim=32, num_attention_heads=4,
            num_key_value_heads=2, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, num_experts=4, first_expert_held=4,
            num_experts_per_tok=3, moe_intermediate_size=32,
            shared_expert_intermediate_size=32,
            published={"num_experts": 16}, vocab_size=320, sequence_length=128,
            flash_blocks=[32, 32], flash_interpret=True)  # 320: no layer's width

# Qwen/Qwen3-Next-80B-A3B-Instruct's config.json as the catalog has it
CATALOG = {"decoder_sparse_step": 1, "full_attention_interval": 4,
           "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
           "linear_key_head_dim": 128, "linear_num_key_heads": 16,
           "linear_num_value_heads": 32, "linear_value_head_dim": 128,
           "max_position_embeddings": 262144, "mlp_only_layers": [],
           "model_type": "qwen3_next", "moe_intermediate_size": 512,
           "norm_topk_prob": True, "num_attention_heads": 16,
           "num_experts": 512, "num_experts_per_tok": 10,
           "num_hidden_layers": 48, "num_key_value_heads": 2,
           "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
           "rope_scaling": None, "rope_theta": 10000000,
           "shared_expert_intermediate_size": 512,
           "tie_word_embeddings": False, "use_sliding_window": False,
           "vocab_size": 151936}


def _real():
    return mf.cell(mf.load(), CELL)["config"]


def _tiny_config(**changes):
    config = copy.deepcopy(_real())
    config.update(TINY)
    config.update(changes)
    return config


def test_the_manifest_with_the_sixth_cell_is_sound():
    manifest = mf.load()
    assert mf.check(manifest) == []
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "qwen3_next_80b_a3b",
                    "traffic": "ssgd_longseq_1chip", "chips": 1}
    # an addition at the ends when it came (PR 36): right behind the fifth
    # cell and its configuration, wherever the ends are now
    cells = [w["name"] for w in manifest["workloads"]]
    configs = [c["name"] for c in manifest["configs"]]
    assert cells[cells.index(CELL) - 1] == "laguna_s_2_1.ssgd_1seq_1chip"
    assert configs[configs.index("qwen3_next_80b_a3b") - 1] == "laguna_s_2_1"
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "gdn_core_ms", "gdn_core_roofline_pct", "gdn_mix_ms", "gattn_core_ms",
        "gattn_core_roofline_pct", "moe_held_ms"]
    at = manifest["per_layer"].index(mine[0])
    assert manifest["per_layer"][at:at + 6] == mine  # the six it was added with
    assert {m["source"] for m in mine} == {"device_trace"}
    assert {m["moves"] for m in mine} == {"step_ms_p50"}
    assert {m["layer"] for m in mine} == {"Kernels", "Model"}
    # the lists this cell joined later (PR 38): head and optimizer
    assert [m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", []) and m not in mine] == [
        "optimizer_ms", "head_loss_ms"]


def test_the_configuration_is_the_catalogs_but_for_its_cut():
    manifest = mf.load()
    config = _real()
    differs = sorted(k for k, v in CATALOG.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 32, 18992)
    assert config["published"] == {k: CATALOG[k] for k in config["reduced"]}
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["num_experts"] * 16 == CATALOG["num_experts"]
    assert qwen3_next.layer_types(config) == ["linear_attention"] * 3 + [
        "full_attention"]
    (entry,) = [c for c in manifest["configs"] if c["name"] == "qwen3_next_80b_a3b"]
    assert entry["source"] == config["source"] and entry["source"].endswith(
        "Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert "16 chips" in config["deployment"] and len(config["assumed"]) >= 10
    assert any("multi-token" in line for line in config["assumed"])
    assert config["sequence_length"] == 16384
    assert config["recomputed_layer_types"] == ["linear_attention"]
    traffic = mf.cell(manifest, CELL)["traffic"]
    assert (traffic["per_chip_batch"], traffic["pool"], traffic["mesh"]) == (
        1, 8, {"dp": 1})
    assert (traffic["launcher"], traffic["step"], traffic["placement"]) == (
        "none", "ssgd", "shard_batch")
    assert traffic["optimizer"] == {"name": "adamw", "learning_rate": 0.0003}


def test_the_cut_holds_625_7_million_parameters():
    """ISSUE 36's count, by `eval_shape`: 33.72 M in a DeltaNet mixer, 27.26
    M in the attention mixer, 104.86 M in a feed-forward, 2 x 38.90 M in
    embedding and head; 10.0e9 bytes at 16 a parameter."""
    state = jax.eval_shape(lambda: qwen3_next.init(_real(), 0))

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    delta, attention = state["layers"]
    feed_forward = {k: v for k, v in attention.items()
                    if k in ("router", "w_gate", "w_up", "w_down", "shared_gate",
                             "shared_up", "shared_down", "w_shared_gate")}
    assert size(feed_forward) == pytest.approx(104.86e6, rel=1e-4)
    assert size(delta) / 3 - size(feed_forward) == pytest.approx(33.72e6, rel=2e-4)
    assert size(attention) - size(feed_forward) == pytest.approx(27.26e6, rel=5e-4)
    assert size(state["embed"]) == size(state["lm_head"]) == 18992 * 2048
    assert size(state) == 625_667_136 and 10.0e9 < 16 * size(state) < 10.02e9
    assert size(state) == pytest.approx(625.7e6, rel=1e-4)
    mc = qwen3_next.model_config(_real())
    assert [(k.mixer, k.layer_remat, n) for k, n in mc.stacks] == [
        ("gated_delta", True, 3), ("attention", False, 1)]
    assert mc.experts_held == (0, 32) and mc.n_experts == 512 and mc.top_k == 10
    assert mc.delta_heads == (16, 32, 128) and mc.head_dim == 256
    assert mc.kv_heads == 2 and mc.rotary_share == 0.25 and mc.rope_theta == 1e7


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("mlp_only_layers", [0]), ("norm_topk_prob", False),
    ("rope_scaling", {"type": "yarn"}), ("use_sliding_window", True),
    ("linear_value_head_dim", 32)])
def test_the_family_refuses_a_layer_it_does_not_run(key, value):
    with pytest.raises(ValueError, match="as published"):
        qwen3_next.model_config(_tiny_config(**{key: value}))


# --- operation and byte counts, by hand --------------------------------------

def test_core_operations_and_bytes_by_hand():
    """One sequence of 128 tokens. The delta rule, 4 value heads of 16 x 16:
    7 x 16 x 16 operations a head and position forward, three times that
    with the backward pass. The softmax core, 4 heads of 32: 128 x 128 / 2
    pairs a head, 32 multiply-adds each, six matmuls."""
    config = _tiny_config()
    assert qwen3_next.delta_core_flops_per_sample(config) == 3 * 7 * 16 * 16 * 4 * 128
    # q, k, dq at 2 key heads (6 arrays), v, o, do, dv at 4 (5), bf16; g, beta
    # and their cotangents (6) float32
    assert qwen3_next.delta_core_bytes_per_sample(config) == 128 * (
        6 * 2 * 16 * 2 + 5 * 4 * 16 * 2 + 6 * 4 * 4)
    assert qwen3_next.attn_core_flops_per_sample(config) == 6 * (2 * 8192 * 4 * 32)
    assert qwen3_next.attn_core_bytes_per_sample(config) == 6 * (4 + 2) * 128 * 32 * 2
    real = _real()
    # the issue's arithmetic: 6.6 T in the causal core, 0.18 T a delta layer
    assert qwen3_next.attn_core_flops_per_sample(real) == pytest.approx(6.597e12, rel=1e-3)
    assert qwen3_next.delta_core_flops_per_sample(real) == pytest.approx(0.1804e12, rel=1e-3)
    assert qwen3_next.delta_core_bytes_per_sample(real) == pytest.approx(1.0863e9, rel=1e-3)
    # on the v5e the rule is bound by its bytes, the softmax core by operations
    peaks = harness.load_peaks("TPU v5 lite")
    for flops, moved, ratio in (
            (qwen3_next.delta_core_flops_per_sample,
             qwen3_next.delta_core_bytes_per_sample, 0.69),
            (qwen3_next.attn_core_flops_per_sample,
             qwen3_next.attn_core_bytes_per_sample, 30.3)):
        t_flops = flops(real) / peaks["bf16_flops"]
        t_bytes = moved(real) / peaks["hbm_bytes_per_s"]
        assert t_flops / t_bytes == pytest.approx(ratio, rel=1e-2)


def test_flops_per_sample_by_hand():
    """Per token: a DeltaNet mixer (W_qkvz 64 x 192, W_ba 64 x 8, 4 taps over
    128 channels, W_o 64 x 64), the attention mixer (W_q 64 x 256, W_k and
    W_v 64 x 64, W_o 128 x 64); in every layer the router over 16, the shared
    expert and its gate and 3 x 4 / 16 of a routed expert; the head 320 x
    64; 2 operations a multiply-add, x 3 for forward and backward, but x 2
    for a router that is not trained (no weight-gradient product), as the
    cell's are; the cores beside them."""
    config = _tiny_config()
    delta = 64 * 192 + 64 * 8 + 4 * 128 + 64 * 64
    attention = 64 * 256 + 2 * 64 * 64 + 128 * 64
    assert qwen3_next.mixer_params_per_token(config, "linear_attention") == delta
    assert qwen3_next.mixer_params_per_token(config, "full_attention") == attention
    expert = 3 * 64 * 32
    sparse = 64 * 16 + expert + 64 + 0.75 * expert
    params = 320 * 64 + 3 * delta + attention + 4 * sparse
    assert qwen3_next.expected_expert_passes(config) == 0.75
    assert qwen3_next.matmul_params_per_token(config) == params == 152_832
    cores = 3 * (3 * 7 * 16 * 16 * 4 * 128) + 6 * (2 * 8192 * 4 * 32)
    assert config["routers_trained"] is False
    assert qwen3_next.flops_per_sample(config) == (
        3 * 2 * params * 128 - 4 * 2 * 64 * 16 * 128 + cores)
    assert qwen3_next.flops_per_sample({**config, "routers_trained": True}) == (
        3 * 2 * params * 128 + cores)
    real = _real()
    assert qwen3_next.expected_expert_passes(real) == 0.625
    # the issue's arithmetic: 192 M matmul parameters a token, 26 T a step
    assert qwen3_next.matmul_params_per_token(real) == pytest.approx(192.0e6, rel=1e-3)
    assert qwen3_next.flops_per_sample({**real, "routers_trained": True}) == (
        pytest.approx(26.0e12, rel=2e-3))
    assert qwen3_next.flops_per_sample(real) == pytest.approx(
        26.009e12 - 4 * 2 * 2048 * 512 * 16384, rel=1e-4)


def test_host_batches_come_from_the_seed_uniform_over_the_slice():
    config = _tiny_config(sequence_length=4096)
    a = qwen3_next.host_batch(config, 2**31 + 11, 3, 2)
    b = qwen3_next.host_batch(config, 2**31 + 11, 3, 2)
    c = qwen3_next.host_batch(config, 2**31 + 12, 3, 2)
    assert a.shape == (2, 4097) and a.dtype == np.int32
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < 320
    counts = np.bincount(a.ravel(), minlength=320)
    assert counts.min() > 5 and counts.max() < 64 and 150 < np.median(a) < 170
    real = qwen3_next.host_batch(_real(), 2**31 + 11, 0, 1)
    assert real.shape == (1, 16385) and real.max() < 18992
    assert np.bincount(real.ravel(), minlength=18992).max() < 12


# --- the program against the reference --------------------------------------

def _both(dtype, seed=5):
    config = _tiny_config(compute_dtype=dtype)
    state = qwen3_next.init(config, seed)
    sample = qwen3_next.host_batch(config, seed, 0, 2)
    got = qwen3_next.program_loss_and_grads(config)(state, sample)
    want = qwen3_next.reference_loss_and_grads(config, state, sample)
    return config, state, sample, got, want


def test_reference_equals_program_in_float32():
    config, state, sample, (loss, grads), (ref_loss, ref_grads) = _both("float32")
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    assert harness.relative_error(grads, ref_grads) <= 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    assert qwen3_next.differing_choices(config, state, sample) == 0
    stats = qwen3_next.routing_stats(config, state, sample)
    assert stats["dropped"] == [0, 0, 0, 0] and stats["layer"] == [0, 1, 2, 3]
    assert stats["held_rows"] == np.sum(stats["counts"], axis=1).tolist()


def test_bfloat16_program_is_within_the_familys_tolerances():
    _, _, _, (loss, grads), (ref_loss, ref_grads) = _both("bfloat16")
    assert abs(float(loss) - float(ref_loss)) <= qwen3_next.LOSS_RTOL * abs(float(ref_loss))
    error = harness.relative_error(grads, ref_grads)
    assert 1e-4 < error <= qwen3_next.GRAD_RTOL, error


@pytest.mark.parametrize("blocks", [
    dict(query_block=32), dict(position_block=16), dict(head_block=1)])
def test_the_reference_computes_in_blocks_what_it_computes_at_once(blocks):
    config = _tiny_config(compute_dtype="float32")
    state = qwen3_next.init(config, 3)
    sample = qwen3_next.host_batch(config, 3, 0, 1)
    from benchmark.reference import qwen3_next as reference

    whole = dict(qwen3_next._hyper(config), query_block=128, position_block=128,
                 head_block=2)
    at_once = reference.loss_and_grads(state, sample, **whole)
    in_blocks = reference.loss_and_grads(state, sample, **{**whole, **blocks})
    assert float(at_once[0]) == pytest.approx(float(in_blocks[0]), rel=1e-6)
    assert harness.relative_error(in_blocks[1], at_once[1]) <= 1e-5


def test_the_reference_imports_nothing_of_the_program():
    import benchmark.reference.qwen3_next as reference

    with open(reference.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "kungfu_tpu" in line]
    assert "pallas" not in text and "custom_vjp" not in text
    assert qwen3_next.REFERENCE_SAMPLES == 1


def test_the_cells_program_holds_to_its_declared_precision():
    config = _tiny_config()
    state = jax.eval_shape(lambda: qwen3_next.init(config, 0))
    sample = qwen3_next.host_batch(config, 0, 0, qwen3_next.REFERENCE_SAMPLES)
    traced = qwen3_next.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, qwen3_next.head_width(config),
                                    traced.jaxpr, state, state) == []
    low = _tiny_config(param_dtype="bfloat16")
    assert harness.precision_faults(low, qwen3_next.head_width(low), traced.jaxpr,
                                    state, state)


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
    assert record["flops_per_sample"] == qwen3_next.flops_per_sample(cell["config"])
    json.dumps(record)
    assert all(v > 0 for v in end_to_end.values(record).values())
    with pytest.raises(RuntimeError, match="chip runs only"):
        end_to_end.result_line(record, None, m)


# --- the six readers on a drawn trace ----------------------------------------

MS = 2_000_000  # a unit of the drawing below, in ns: 2 ms
# Two steps of 80 units on one chip, each alike:
#   gdn.proj [0, 4)  gdn.conv [4, 5)  gdn.local [5, 8)  gdn.scan [8, 12)
#   gdn.norm [12, 13)  attn.proj [13, 15)  attn.core.fwd [15, 19)
#   attn.gate [19, 19.5)  router [19.5, 21)  gmm.fwd [21, 23)
#   shared.fwd [23, 24)  norm [24, 24.5) (under `moe` alone)  head [24.5, 28)
#   attn.core.bwd [28, 38)  gdn.again [38, 45) (the rule's forward, run again
#   in the backward pass)  gdn.bwd [45, 59)  gdn.proj.bwd [59, 67)
#   gmm.bwd [67, 71)  shared.bwd [71, 73)  adamw [73, 76) (under `optimizer`)
STEP_OPS = [("gdn.proj", 0, 4), ("gdn.conv", 4, 5), ("gdn.local", 5, 8),
            ("gdn.scan", 8, 12), ("gdn.norm", 12, 13), ("attn.proj", 13, 15),
            ("attn.core.fwd", 15, 19), ("attn.gate", 19, 19.5),
            ("router", 19.5, 21), ("gmm.fwd", 21, 23), ("shared.fwd", 23, 24),
            ("norm", 24, 24.5), ("head", 24.5, 28), ("attn.core.bwd", 28, 38),
            ("gdn.again", 38, 45), ("gdn.bwd", 45, 59), ("gdn.proj.bwd", 59, 67),
            ("gmm.bwd", 67, 71), ("shared.bwd", 71, 73), ("adamw", 73, 76)]
DRAWN = {
    "chips": [{"plane": "/device:TPU:0", "program": "jit_step",
               "steps": [[0, 80 * MS], [80 * MS, 160 * MS]],
               "ops": [[name, int((at + a) * MS), int((at + b) * MS)]
                       for at in (0, 80) for name, a, b in STEP_OPS]}],
    "host": [], "lines": {},
}
FWD = "jit(step)/shard_map/jvp()/while/body/closed_call"
BWD = "jit(step)/shard_map/transpose(jvp())/while/body/closed_call"
AGAIN = f"{BWD}/checkpoint/rematted_computation"
SCOPES = {
    "gdn.proj": f"{FWD}/gdn/while/body/checkpoint/gdn_proj/dot_general",
    "gdn.conv": f"{FWD}/gdn/while/body/checkpoint/gdn_conv/mul",
    "gdn.local": f"{FWD}/gdn/while/body/checkpoint/gdn_core/dot_general",
    "gdn.scan": f"{FWD}/gdn/while/body/checkpoint/gdn_core/while/body/dot_general",
    "gdn.norm": f"{FWD}/gdn/while/body/checkpoint/gdn_norm/mul",
    "gdn.again": f"{AGAIN}/gdn/while/body/checkpoint/gdn_core/while/body/dot_general",
    "gdn.bwd": f"{BWD}/checkpoint/gdn/while/body/checkpoint/gdn_core/while/body/dot_general",
    "gdn.proj.bwd": f"{BWD}/checkpoint/gdn/while/body/checkpoint/gdn_proj/dot_general",
    "attn.proj": f"{FWD}/attn/dot_general",
    "attn.core.fwd": f"{FWD}/attn/attn_full/attn_core/pallas_call",
    "attn.core.bwd": f"{BWD}/attn/attn_full/attn_core/pallas_call",
    "attn.gate": f"{FWD}/attn/attn_gate/checkpoint/mul",
    "router": f"{FWD}/moe/moe_router/dot_general",
    "gmm.fwd": "ragged-dot-none",
    "gmm.bwd": "ragged-dot-none",
    "shared.fwd": f"{FWD}/moe/moe_shared/dot_general",
    "shared.bwd": f"{BWD}/moe/moe_shared/dot_general",
    "norm": f"{FWD}/moe/checkpoint/rsqrt",
    "head": "jit(step)/shard_map/jvp(head_loss)/dot_general",
    "adamw": "jit(step)/shard_map/optimizer/optimizer_update/add",
}


def _record(samples_per_step=1):
    return {"workload": CELL, "scopes": SCOPES, "samples_per_step": samples_per_step,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_drawn_times():
    record = _record()
    assert gdn_core_ms.read(record, DRAWN) == pytest.approx(2 * (3 + 4 + 7 + 14))
    # `gdn` less the rule: projections, convolution and gated norm
    assert gdn_mix_ms.read(record, DRAWN) == pytest.approx(2 * (4 + 1 + 1 + 8))
    assert gattn_core_ms.read(record, DRAWN) == pytest.approx(2 * (4 + 10))
    assert moe_held_ms.read(record, DRAWN) == pytest.approx(
        2 * (1.5 + 2 + 1 + 0.5 + 4 + 2))


def test_drawn_shares_of_the_roofline():
    """At the real widths: one sequence of 16,384 tokens a step, three
    DeltaNet layers bound by their bytes, one softmax core by operations."""
    record = _record()
    peaks = harness.load_peaks("TPU v5 lite")
    moved = 3 * 16384 * (6 * 16 * 128 * 2 + 5 * 32 * 128 * 2 + 6 * 32 * 4)
    assert moved / peaks["hbm_bytes_per_s"] > (
        3 * 3 * 7 * 128 * 128 * 32 * 16384 / peaks["bf16_flops"])
    assert gdn_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * moved / peaks["hbm_bytes_per_s"] / 56e-3)
    core = 6 * (2 * (16384 * 16384 / 2) * 16 * 256) / peaks["bf16_flops"]
    assert gattn_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * core / 28e-3)
    # a step of two sequences has twice the work in the same drawn time
    assert gdn_core_roofline_pct.read(_record(2), DRAWN) == pytest.approx(
        2 * gdn_core_roofline_pct.read(record, DRAWN))
    assert 0 < gdn_core_roofline_pct.read(record, DRAWN) <= 100


def test_no_roofline_share_can_pass_100_by_construction():
    """The roofs count what a kernel must do and nothing it might skip: the
    recurrence's own operations (fewer than the chunked form's), every
    array once each way, the causal half of the softmax core."""
    real = _real()
    d = real["linear_key_head_dim"]
    chunked_forward = (2 * 64 * d * 2 + 2 * 64 * 2 * d + 3 * 2 * d * d + 2 * 64 * d)
    assert 7 * d * d < chunked_forward
    assert qwen3_next.attn_core_flops_per_sample(real) == (
        6 * 2 * 16384 * 16384 / 2 * 16 * 256)


READERS = (gdn_core_ms, gdn_core_roofline_pct, gdn_mix_ms, gattn_core_ms,
           gattn_core_roofline_pct, moe_held_ms)


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
              "program_memory": {"total_bytes": 15_400_000_000},
              "memory_stats_peak_bytes": 1, "correct": True, "attempted": 20,
              "failed": 0}
    line = end_to_end.result_line(record, DRAWN, manifest)
    mine = {x["name"] for x in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(line["metrics"]) == mine
    assert {"gdn_core_ms", "gdn_core_roofline_pct", "gdn_mix_ms", "gattn_core_ms",
            "gattn_core_roofline_pct", "moe_held_ms"} <= mine
    assert not {"full_core_ms", "moe_share_ms", "moe_ms", "flash_core_ms"} & mine
    # head and optimizer are read here since PR 38
    assert line["metrics"]["head_loss_ms"]["value"] == pytest.approx(2 * 3.5)
    assert line["metrics"]["optimizer_ms"]["value"] == pytest.approx(2 * 3.0)
    assert mf.check_result_line(line, manifest, CELL, traced=True) == []
