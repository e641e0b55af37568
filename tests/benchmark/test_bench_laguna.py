"""The Laguna family, its cell and its eight per-layer metrics (PR 33): the
whole of `harness.measure` on the new cell at tiny size on the CPU mesh, the
operation counts against sums made by hand, the batches, the readers against
a drawn trace, and the configuration file against the catalog's numbers."""

import copy
import json
import time

import jax
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.families import laguna
from benchmark.launchers.none import OneProcess
from drawn_setup import child_marks, drawn_setup
from benchmark.layer_metrics import (attn_proj_ms, full_core_ms,
                                     full_core_roofline_pct,
                                     moe_share_dispatch_ms,
                                     moe_share_experts_ms, moe_share_ms,
                                     window_core_ms, window_core_roofline_pct)

CELL = "laguna_s_2_1.ssgd_1seq_1chip"
# every mechanism on, at the tests' size (tests/test_laguna_layers.py); the
# kernel in interpret mode by a key of the configuration
TINY = dict(hidden_size=64, intermediate_size=96, head_dim=16,
            num_attention_heads=4, num_key_value_heads=2,
            num_attention_heads_per_layer=[4, 6, 6, 6] * 12, sliding_window=16,
            num_experts=4, first_expert_held=4, num_experts_per_tok=3,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            published={"num_experts": 16}, vocab_size=256, sequence_length=64,
            flash_blocks=[16, 16], flash_interpret=True)

# poolside/Laguna-S-2.1's config.json as the catalog has it: its numbers, and
# the first entries of its per-layer lists
CATALOG = {"model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
           "intermediate_size": 12288, "num_hidden_layers": 48,
           "num_attention_heads": 48, "num_key_value_heads": 8,
           "head_dim": 128, "max_position_embeddings": 1048576,
           "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
           "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
           "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
           "decoder_sparse_step": 1, "mlp_only_layers": [0],
           "tie_word_embeddings": False, "gating": "per-head",
           "sliding_window": 512, "moe_apply_router_weight_on_input": False,
           "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0}
FULL_ROPE = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
             "original_max_position_embeddings": 8192, "beta_slow": 1,
             "beta_fast": 32, "attention_factor": 1.4852030263919618,
             "partial_rotary_factor": 0.5}


def _real():
    return mf.cell(mf.load(), CELL)["config"]


def _tiny_config(**changes):
    config = copy.deepcopy(_real())
    config.update(TINY)
    config["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=32, factor=8)
    config.update(changes)
    return config


def test_the_configuration_is_the_catalogs_but_for_its_cut():
    manifest = mf.load()
    config = _real()
    differs = sorted(k for k, v in CATALOG.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 12544)
    assert config["published"] == {k: CATALOG[k] for k in config["reduced"]}
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["rope_parameters"] == {
        "full_attention": FULL_ROPE,
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
    # the source's per-layer lists whole; the period is full, 3 x sliding
    for key, period in (("layer_types", ["full_attention"] + ["sliding_attention"] * 3),
                        ("num_attention_heads_per_layer", [48, 72, 72, 72]),
                        ("gating_types", ["per_head"] * 4)):
        assert config[key] == period * 12, key
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    (entry,) = [c for c in manifest["configs"] if c["name"] == "laguna_s_2_1"]
    assert entry["source"] == config["source"] and entry["source"].endswith(
        "poolside/Laguna-S-2.1/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert "32 chips" in config["deployment"] and len(config["assumed"]) >= 10
    traffic = mf.cell(manifest, CELL)["traffic"]
    assert (traffic["per_chip_batch"], traffic["pool"], traffic["mesh"]) == (
        1, 8, {"dp": 1})
    assert traffic["optimizer"] == {"name": "adamw", "learning_rate": 0.0003}


def test_the_cut_holds_811_million_parameters():
    """ISSUE 33's count, by `eval_shape`: 157.43 M in layer 0, 148.86 M in
    each sliding layer, 129.91 M in layer 4, 2 x 38.54 M in embedding and
    head; 12.98e9 bytes at 16 a parameter."""
    state = jax.eval_shape(lambda: laguna.init(_real(), 0))

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    layers = [size(stack) for stack in state["layers"]]
    assert layers == pytest.approx([157.43e6, 3 * 148.86e6, 129.91e6], rel=1e-4)
    assert size(state["embed"]) == size(state["lm_head"]) == 12544 * 3072
    assert size(state) == 811_017_216 and 12.9e9 < 16 * size(state) < 13.0e9
    mc = laguna.model_config(_real())
    assert [(k.n_heads, k.window, k.ffn, n) for k, n in mc.stacks] == [
        (48, 0, "swiglu", 1), (72, 512, "moe", 3), (48, 0, "moe", 1)]
    assert mc.stacks[1][0].experts_held == (0, 8) and mc.n_experts == 256


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("gating", "per-layer"),
    ("router_scores", "sigmoid"), ("moe_router_logit_softcapping", 30),
    ("mlp_only_layers", []), ("attention_bias", True)])
def test_the_family_refuses_a_layer_it_does_not_run(key, value):
    with pytest.raises(ValueError, match="as published"):
        laguna.model_config(_tiny_config(**{key: value}))


# --- operation counts, by hand ----------------------------------------------

def test_core_operations_and_bytes_by_hand():
    """One sequence of 64 tokens. A full layer, 4 heads x 16: QK^T is 64 x
    64 / 2 pairs a head, 16 multiply-adds each, 2 operations; six such
    matmuls. A sliding layer, 6 heads, window 16: 64 x 16 - 16^2 / 2 pairs."""
    config = _tiny_config()
    layers = laguna._layers(config)
    full, sliding = layers[0], layers[1]
    assert (full["heads"], full["window"]) == (4, 0)
    assert (sliding["heads"], sliding["window"]) == (6, 16)
    assert laguna.seen_pairs(config, 0) == 2048 and laguna.seen_pairs(config, 16) == 896
    assert laguna.core_flops_per_sample(config, full) == 6 * (2 * 2048 * 4 * 16)
    assert laguna.core_flops_per_sample(config, sliding) == 6 * (2 * 896 * 6 * 16)
    # 6 arrays at the query heads, 6 at the 2 key/value heads, bf16
    assert laguna.core_bytes_per_sample(config, full) == 6 * (4 + 2) * 64 * 16 * 2
    assert laguna.core_bytes_per_sample(config, sliding) == 6 * (6 + 2) * 64 * 16 * 2
    real = _real()
    full, sliding = laguna._layers(real)[0], laguna._layers(real)[1]
    # the issue's arithmetic: 2.47 T a causal core, 0.45 T a band core
    assert laguna.core_flops_per_sample(real, full) == pytest.approx(2.474e12, rel=1e-3)
    assert laguna.core_flops_per_sample(real, sliding) == pytest.approx(0.449e12, rel=2e-3)
    # both bound by operations on the v5e, the band core not by much: 2.28
    # ms of operations against 1.23 ms of bytes a layer (12.56 against 0.86)
    peaks = harness.load_peaks("TPU v5 lite")
    for layer, ratio in ((full, 14.6), (sliding, 1.86)):
        t_flops = laguna.core_flops_per_sample(real, layer) / peaks["bf16_flops"]
        t_bytes = laguna.core_bytes_per_sample(real, layer) / peaks["hbm_bytes_per_s"]
        assert t_flops / t_bytes == pytest.approx(ratio, rel=1e-2)


def test_flops_per_sample_by_hand():
    """Per token: the projections (q and o at the layer's heads, k and v at
    2), the head gate; layer 0's dense feed-forward; in an expert layer the
    router over 16, the shared expert and 3 x 4 / 16 of a routed expert; the
    head 256 x 64; 2 operations a multiply-add, x 3 for forward and
    backward; the cores beside them."""
    config = _tiny_config()

    def attention(heads):
        return 2 * 64 * heads * 16 + 2 * 64 * 2 * 16 + 64 * heads

    expert = 3 * 64 * 32
    sparse = 64 * 16 + expert + 0.75 * expert
    params = (256 * 64 + attention(4) + 3 * 64 * 96
              + 3 * (attention(6) + sparse) + attention(4) + sparse)
    assert laguna.expected_expert_passes(config) == 0.75
    assert laguna.matmul_params_per_token(config) == params == 157_312
    cores = 2 * 6 * (2 * 2048 * 4 * 16) + 3 * 6 * (2 * 896 * 6 * 16)
    assert laguna.flops_per_sample(config) == 3 * 2 * params * 64 + cores
    real = _real()
    assert laguna.expected_expert_passes(real) == 0.3125
    # the issue's arithmetic: 482.3 M matmul parameters a token, about 30
    # TFLOP a step
    assert laguna.matmul_params_per_token(real) == pytest.approx(482.3e6, rel=1e-3)
    assert laguna.flops_per_sample(real) == pytest.approx(30.0e12, rel=2e-3)


def test_host_batches_come_from_the_seed_uniform_over_the_slice():
    config = _tiny_config(sequence_length=4096)
    a = laguna.host_batch(config, 2**31 + 11, 3, 2)
    b = laguna.host_batch(config, 2**31 + 11, 3, 2)
    c = laguna.host_batch(config, 2**31 + 12, 3, 2)
    assert a.shape == (2, 4097) and a.dtype == np.int32
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < 256
    # uniform: every id about 32 times, none 1/23 of all tokens
    counts = np.bincount(a.ravel(), minlength=256)
    assert counts.min() > 8 and counts.max() < 80 and 120 < np.median(a) < 136
    real = laguna.host_batch(_real(), 2**31 + 11, 0, 1)
    assert real.shape == (1, 8193) and real.max() < 12544
    assert np.bincount(real.ravel(), minlength=12544).max() < 12


# --- the program against the reference --------------------------------------

def _both(dtype, seed=5):
    config = _tiny_config(compute_dtype=dtype)
    state = laguna.init(config, seed)
    sample = laguna.host_batch(config, seed, 0, 2)
    got = laguna.program_loss_and_grads(config)(state, sample)
    want = laguna.reference_loss_and_grads(config, state, sample)
    return config, state, sample, got, want


def test_reference_equals_program_in_float32():
    config, state, sample, (loss, grads), (ref_loss, ref_grads) = _both("float32")
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    assert harness.relative_error(grads, ref_grads) <= 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    assert laguna.differing_choices(config, state, sample) == 0
    stats = laguna.routing_stats(config, state, sample)
    assert stats["dropped"] == [0, 0, 0, 0] and stats["layer"] == [1, 2, 3, 4]
    assert stats["held_rows"] == np.sum(stats["counts"], axis=1).tolist()


def test_bfloat16_program_is_within_the_familys_tolerances():
    _, _, _, (loss, grads), (ref_loss, ref_grads) = _both("bfloat16")
    assert abs(float(loss) - float(ref_loss)) <= laguna.LOSS_RTOL * abs(float(ref_loss))
    error = harness.relative_error(grads, ref_grads)
    assert 1e-4 < error <= laguna.GRAD_RTOL, error


def test_the_reference_computes_in_blocks_what_it_computes_at_once():
    config = _tiny_config(compute_dtype="float32")
    state = laguna.init(config, 3)
    sample = laguna.host_batch(config, 3, 0, 1)
    from benchmark.reference import laguna as reference

    hyper = laguna._hyper(config)
    whole = reference.loss_and_grads(state, sample, **{**hyper, "query_block": 64})
    blocks = reference.loss_and_grads(state, sample, **{**hyper, "query_block": 16})
    assert float(whole[0]) == pytest.approx(float(blocks[0]), rel=1e-6)
    assert harness.relative_error(blocks[1], whole[1]) <= 1e-5


def test_the_reference_imports_nothing_of_the_program():
    import benchmark.reference.laguna as reference

    with open(reference.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "kungfu_tpu" in line]
    assert laguna.REFERENCE_SAMPLES == 1


def test_the_cells_program_holds_to_its_declared_precision():
    config = _tiny_config()
    state = jax.eval_shape(lambda: laguna.init(config, 0))
    sample = laguna.host_batch(config, 0, 0, laguna.REFERENCE_SAMPLES)
    traced = laguna.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, laguna.head_width(config),
                                    traced.jaxpr, state, state) == []
    low = _tiny_config(param_dtype="bfloat16")
    assert harness.precision_faults(low, laguna.head_width(low), traced.jaxpr,
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
    assert record["flops_per_sample"] == laguna.flops_per_sample(cell["config"])
    json.dumps(record)
    assert all(v > 0 for v in end_to_end.values(record).values())
    with pytest.raises(RuntimeError, match="chip runs only"):
        end_to_end.result_line(record, None, m)


# --- the eight readers on a drawn trace -------------------------------------

MS = 2_000_000  # a unit of the drawing below, in ns: 2 ms
# Two steps of 60 units on one chip, each alike:
#   proj.fwd [0, 4)  rope [4, 4.5)  window.fwd [4.5, 5.5)  full.fwd [5.5, 9.5)
#   gate [9.5, 10)  router [10, 11)  sort [11, 13)  gmm.fwd [13, 14)
#   shared.fwd [14, 16)  combine [16, 17)  norm [17, 17.25) (under `moe` alone)
#   dense [17.25, 22) (under `ffn`)  head [22, 25)
#   full.bwd [25, 35)  window.bwd [35, 37)  window.again [37, 38) (the
#   sliding layers' forward kernel, run again in the backward pass)
#   proj.bwd [38, 46)  gmm.bwd [46, 48)  shared.bwd [48, 52)  scatter [52, 53)
#   adamw [53, 56) (under `optimizer`)
STEP_OPS = [("proj.fwd", 0, 4), ("rope", 4, 4.5), ("window.fwd", 4.5, 5.5),
            ("full.fwd", 5.5, 9.5), ("gate", 9.5, 10), ("router", 10, 11),
            ("sort", 11, 13), ("gmm.fwd", 13, 14), ("shared.fwd", 14, 16),
            ("combine", 16, 17), ("norm", 17, 17.25), ("dense", 17.25, 22),
            ("head", 22, 25), ("full.bwd", 25, 35), ("window.bwd", 35, 37),
            ("window.again", 37, 38), ("proj.bwd", 38, 46), ("gmm.bwd", 46, 48),
            ("shared.bwd", 48, 52), ("scatter", 52, 53), ("adamw", 53, 56)]
DRAWN = {
    "chips": [{"plane": "/device:TPU:0", "program": "jit_step",
               "steps": [[0, 60 * MS], [60 * MS, 120 * MS]],
               "ops": [[name, int((at + a) * MS), int((at + b) * MS)]
                       for at in (0, 60) for name, a, b in STEP_OPS]}],
    "host": [], "lines": {},
}
FWD = "jit(step)/shard_map/jvp()/while/body/closed_call"
BWD = "jit(step)/shard_map/transpose(jvp())/while/body/closed_call"
SCOPES = {
    "proj.fwd": f"{FWD}/attn/dot_general",
    "proj.bwd": f"{BWD}/attn/dot_general",
    "rope": f"{FWD}/attn/rope/checkpoint/mul",
    "gate": f"{FWD}/attn/attn_gate/checkpoint/mul",
    "window.fwd": f"{FWD}/attn/attn_window/attn_core/pallas_call",
    "window.bwd": f"{BWD}/attn/attn_window/attn_core/pallas_call",
    "window.again": f"{BWD}/checkpoint/attn/attn_window/attn_core/pallas_call",
    "full.fwd": f"{FWD}/attn/attn_full/attn_core/pallas_call",
    "full.bwd": f"{BWD}/attn/attn_full/attn_core/pallas_call",
    "router": f"{FWD}/moe/moe_router/dot_general",
    "sort": f"{FWD}/moe/moe_dispatch/sort",
    # the held part's ops lie under the branch of its buffer's size
    "gmm.fwd": "ragged-dot-none",
    "gmm.bwd": "ragged-dot-none",
    "shared.fwd": f"{FWD}/moe/moe_shared/dot_general",
    "shared.bwd": f"{BWD}/moe/moe_shared/dot_general",
    "combine": f"{FWD}/moe/checkpoint/cond/branch_1_fun/moe_combine/scatter-add",
    "scatter": f"{BWD}/moe/checkpoint/cond/branch_1_fun/moe_dispatch/scatter-add",
    "norm": f"{FWD}/moe/checkpoint/rsqrt",
    "dense": f"{FWD}/ffn/dot_general",
    "head": "jit(step)/shard_map/jvp(head_loss)/dot_general",
    "adamw": "jit(step)/shard_map/optimizer/optimizer_update/add",
}


def _record(samples_per_step=1):
    return {"workload": CELL, "scopes": SCOPES, "samples_per_step": samples_per_step,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_drawn_times():
    record = _record()
    assert window_core_ms.read(record, DRAWN) == pytest.approx(2 * (1 + 2 + 1))
    assert full_core_ms.read(record, DRAWN) == pytest.approx(2 * (4 + 10))
    # `attn` less the cores: projections, rope and gate
    assert attn_proj_ms.read(record, DRAWN) == pytest.approx(2 * (4 + 8 + 0.5 + 0.5))
    assert moe_share_dispatch_ms.read(record, DRAWN) == pytest.approx(2 * (1 + 2 + 1 + 1))
    assert moe_share_experts_ms.read(record, DRAWN) == pytest.approx(2 * (1 + 2 + 2 + 4))
    assert moe_share_ms.read(record, DRAWN) == pytest.approx(2 * (5 + 9 + 0.25))
    rest = (moe_share_ms.read(record, DRAWN)
            - moe_share_experts_ms.read(record, DRAWN)
            - moe_share_dispatch_ms.read(record, DRAWN))
    assert rest == pytest.approx(0.5)  # the norm, under `moe` alone


def test_drawn_shares_of_the_roofline():
    """At the real widths: one sequence of 8,192 tokens a step, two full
    layers and three sliding ones, both kinds bound by operations."""
    record = _record()
    peaks = harness.load_peaks("TPU v5 lite")
    full = 2 * 6 * (2 * (8192 * 8192 / 2) * 48 * 128) / peaks["bf16_flops"]
    assert full_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * full / 28e-3)
    band = 3 * 6 * (2 * (8192 * 512 - 512 * 512 / 2) * 72 * 128) / peaks["bf16_flops"]
    assert band > 3 * 6 * (72 + 8) * 8192 * 128 * 2 / peaks["hbm_bytes_per_s"]
    assert window_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * band / 8e-3)
    # drawn to be possible: under the roof, over nothing
    assert 0 < full_core_roofline_pct.read(record, DRAWN) <= 100
    assert 0 < window_core_roofline_pct.read(record, DRAWN) <= 100


READERS = (window_core_ms, window_core_roofline_pct, full_core_ms,
           full_core_roofline_pct, attn_proj_ms, moe_share_ms,
           moe_share_dispatch_ms, moe_share_experts_ms)


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
    family): nothing ran under them, 0, and no share of any roof."""
    record = {**_record(), "scopes": {"head": SCOPES["head"]}}
    assert reader.read(record, DRAWN) == 0.0


def test_the_traced_line_holds_the_eight_new_metrics():
    manifest = mf.load()
    record = {**_record(), "traced": True, **drawn_setup(), "chips": 1,
              "window": {"compiles": 0, "t_done": [1.0, 1.4, 1.8, 2.2],
                         "spans": [["bench.input", 1.0, 1.001]]},
              "program_memory": {"total_bytes": 17_300_000_000},
              "memory_stats_peak_bytes": 1, "correct": True, "attempted": 20,
              "failed": 0}
    line = end_to_end.result_line(record, DRAWN, manifest)
    mine = {x["name"] for x in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(line["metrics"]) == mine
    assert {r.__name__.split(".")[-1] for r in READERS} <= mine
    for name in ("attention_core_ms", "flash_core_ms", "moe_ms"):
        assert name not in mine  # other cells' lists, as they were
    # head and optimizer are read here since PR 38
    assert line["metrics"]["head_loss_ms"]["value"] == pytest.approx(2 * 3.0)
    assert line["metrics"]["optimizer_ms"]["value"] == pytest.approx(2 * 3.0)
