"""The OLMoE family, its cell and its six per-layer metrics (PR 27): the
whole of `harness.measure` on the new cell at tiny size over four CPU
devices, the operation counts against sums made by hand, the readers against
a drawn trace, and the configuration file against the catalog's numbers."""

import json
import time

import jax
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.families import olmoe
from benchmark.launchers.none import OneProcess
from drawn_setup import child_marks, drawn_setup
from benchmark.layer_metrics import (expert_ffn_ms, expert_matmul_peak_pct,
                                     flash_core_ms, flash_roofline_pct,
                                     moe_dispatch_ms, moe_ms)

CELL = "olmoe_1b_7b.ssgd_seq4096_1chip"
# every mechanism on, at the tests' size; the kernel in interpret mode by a
# key of the configuration: the backend is never asked
TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, intermediate_size=32, num_experts=8,
            num_experts_per_tok=3, vocab_size=256, max_position_embeddings=64,
            flash_blocks=[32, 32], flash_interpret=True)

# allenai/OLMoE-1B-7B-0125-Instruct's config.json as the catalog has it
CATALOG = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 1024,
           "max_position_embeddings": 4096, "model_type": "olmoe",
           "norm_topk_prob": False, "num_attention_heads": 16,
           "num_experts": 64, "num_experts_per_tok": 8,
           "num_hidden_layers": 16, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "tie_word_embeddings": False, "vocab_size": 50304}


def _tiny_config(**changes):
    return {**mf.cell(mf.load(), CELL)["config"], **TINY, **changes}


def test_the_configuration_is_the_catalogs_but_for_its_depth():
    manifest = mf.load()
    config = mf.cell(manifest, CELL)["config"]
    differs = sorted(k for k, v in CATALOG.items() if config.get(k) != v)
    assert differs == config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 1
    (entry,) = [c for c in manifest["configs"] if c["name"] == "olmoe_1b_7b"]
    assert entry["source"] == config["source"] and entry["source"].endswith(
        "allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json")
    traffic = mf.cell(manifest, CELL)["traffic"]
    assert (traffic["per_chip_batch"], traffic["pool"], traffic["mesh"]) == (
        2, 8, {"dp": 1})
    assert traffic["optimizer"] == {"name": "adamw", "learning_rate": 0.0003}


def test_the_family_refuses_a_layer_it_does_not_run():
    for key, value in (("norm_topk_prob", True), ("num_key_value_heads", 2),
                       ("tie_word_embeddings", True), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match="as published"):
            olmoe.model_config(_tiny_config(**{key: value}))


# --- operation counts, by hand ----------------------------------------------

def test_expert_matmul_operations_by_hand():
    """One token, one layer: 3 experts x 3 matrices of 64 x 32, a
    multiply-add 2 operations, forward once and backward twice."""
    config = _tiny_config()
    by_hand = 3 * (3 * 3 * 2 * 64 * 32)
    assert by_hand == 110_592
    assert olmoe.expert_matmul_flops_per_token(config) == by_hand
    real = mf.cell(mf.load(), CELL)["config"]
    assert olmoe.expert_matmul_flops_per_token(real) == 3 * 8 * 3 * 2 * 2048 * 1024


def test_flash_core_operations_and_bytes_by_hand():
    """One sequence of 64 tokens, one layer, 4 heads x 16: QK^T is 64 x 64
    x 16 multiply-adds a head, 2 operations each, the causal half of it;
    six such matmuls forward and backward."""
    config = _tiny_config()
    one_matmul = 4 * (2 * 64 * 64 * 16) / 2
    assert one_matmul == 262_144
    assert olmoe.flash_core_flops_per_sample(config) == 6 * one_matmul
    assert olmoe.flash_core_bytes_per_sample(config) == 12 * 64 * 64 * 2
    real = mf.cell(mf.load(), CELL)["config"]
    # compute-bound at head size 128: over 240 operations a byte
    assert (olmoe.flash_core_flops_per_sample(real)
            / olmoe.flash_core_bytes_per_sample(real)) > 240


def test_flops_per_sample_by_hand():
    """Per token and layer: wqkv 64 x 192, wo 64 x 64, router 64 x 8, three
    active experts of 3 x 64 x 32; the head 256 x 64; 2 operations a
    multiply-add, x 3 for forward and backward; the core beside them."""
    config = _tiny_config()
    per_layer = 64 * 192 + 64 * 64 + 64 * 8 + 3 * 3 * 64 * 32
    params = 2 * per_layer + 256 * 64
    assert olmoe.matmul_params_per_token(config) == params == 87_040
    want = 3 * 2 * params * 64 + 2 * 6 * 262_144
    assert olmoe.flops_per_sample(config) == want
    real = mf.cell(mf.load(), CELL)["config"]
    # the issue's arithmetic: about 460 MFLOP a token in the layer with its
    # core, 619 in the head
    layer = (6 * (olmoe.matmul_params_per_token(real) - 50304 * 2048)
             + olmoe.flash_core_flops_per_sample(real) / 4096)
    assert 4.4e8 < layer < 4.7e8 and 6 * 50304 * 2048 == pytest.approx(6.18e8, rel=1e-2)


@pytest.mark.parametrize("key,factor", [("num_experts_per_tok", 2),
                                        ("max_position_embeddings", 2)])
def test_counts_scale_with_their_shapes(key, factor):
    config = _tiny_config(num_experts_per_tok=2)
    more = dict(config, **{key: config[key] * factor})
    if key == "num_experts_per_tok":
        assert olmoe.expert_matmul_flops_per_token(more) == (
            factor * olmoe.expert_matmul_flops_per_token(config))
        assert olmoe.flash_core_flops_per_sample(more) == (
            olmoe.flash_core_flops_per_sample(config))
    else:
        assert olmoe.flash_core_flops_per_sample(more) == (
            factor ** 2 * olmoe.flash_core_flops_per_sample(config))


def test_host_batches_come_from_the_seed_and_take_a_large_one():
    config = _tiny_config()
    a = olmoe.host_batch(config, 2**31 + 11, 3, 2)
    b = olmoe.host_batch(config, 2**31 + 11, 3, 2)
    c = olmoe.host_batch(config, 2**31 + 12, 3, 2)
    assert a.shape == (2, 65) and a.dtype == np.int32
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < 256
    assert np.median(a) < 64  # skewed towards the low ids


# --- the program against the reference --------------------------------------

def _both(dtype, seed=5):
    config = _tiny_config(compute_dtype=dtype)
    state = olmoe.init(config, seed)
    sample = olmoe.host_batch(config, seed, 0, 2)
    got = olmoe.program_loss_and_grads(config)(state, sample)
    want = olmoe.reference_loss_and_grads(config, state, sample)
    return config, state, sample, got, want


def test_reference_equals_program_in_float32():
    config, state, sample, (loss, grads), (ref_loss, ref_grads) = _both("float32")
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    assert harness.relative_error(grads, ref_grads) <= 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    assert olmoe.differing_choices(config, state, sample) == 0
    stats = olmoe.routing_stats(config, state, sample)
    assert stats["dropped"] == [0, 0]
    assert np.sum(stats["counts"], axis=1).tolist() == [2 * 64 * 3] * 2


def test_bfloat16_program_is_within_the_familys_tolerances():
    _, _, _, (loss, grads), (ref_loss, ref_grads) = _both("bfloat16")
    assert abs(float(loss) - float(ref_loss)) <= olmoe.LOSS_RTOL * abs(float(ref_loss))
    error = harness.relative_error(grads, ref_grads)
    assert 1e-4 < error <= olmoe.GRAD_RTOL, error


def test_the_reference_imports_nothing_of_the_program():
    import benchmark.reference.olmoe as reference

    with open(reference.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "kungfu_tpu" in line]
    assert olmoe.REFERENCE_SAMPLES == 1


def test_the_cells_program_holds_to_its_declared_precision():
    config = _tiny_config()
    state = jax.eval_shape(lambda: olmoe.init(config, 0))
    sample = olmoe.host_batch(config, 0, 0, olmoe.REFERENCE_SAMPLES)
    traced = olmoe.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, olmoe.head_width(config),
                                    traced.jaxpr, state, state) == []
    low = _tiny_config(param_dtype="bfloat16")
    assert harness.precision_faults(low, olmoe.head_width(low), traced.jaxpr,
                                    state, state)


# --- the whole of measure ----------------------------------------------------

@pytest.fixture(scope="module")
def events():
    return harness.EventCounter()


def test_measure_at_tiny_size_on_four_cpu_devices(events):
    """As `test_bench_loop.test_measure_at_tiny_size_on_four_cpu_devices`
    does for the other two families: state, pool, first step, warm-up,
    probe, window, checks, on a dp = 4 mesh of virtual CPU devices."""
    from kungfu_tpu.parallel import make_mesh

    m = mf.load()
    cell = mf.cell(m, CELL)
    cell["config"].update(TINY)
    cell["traffic"].update(per_chip_batch=2, mesh={"dp": 4})
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
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
    assert record["samples_per_step"] == 8 and record["chips"] == 4
    assert record["flops_per_sample"] == olmoe.flops_per_sample(cell["config"])
    json.dumps(record)
    assert all(v > 0 for v in end_to_end.values(record).values())
    assert record["device"]["platform"] == "cpu"
    with pytest.raises(RuntimeError, match="chip runs only"):
        end_to_end.result_line(record, None, m)


# --- the six readers on a drawn trace ---------------------------------------

MS = 1_000_000
# Two steps of 30 ms on one chip (times in ms). The forward pass of the one
# layer, then its backward, each step alike:
#   flash.fwd [0, 1)   router [1, 1.5)  sort [1.5, 2.5)  gmm.fwd [2.5, 7.5)
#   combine [7.5, 8)   norm [8, 8.25) (under `moe` alone)  head [8.25, 12)
#   gmm.bwd [12, 22)   scatter [22, 23) flash.bwd [23, 25.5)
#   copy [26, 27): the compiler's own, in no scope   silu [27, 27.5)
#   adamw [27.5, 29.5) (under `optimizer`)
# gmm.fwd and gmm.bwd are the grouped-matmul kernels, which carry XLA's name
# and no scope; silu is the gate recomputed between them, under `moe_experts`
STEP_OPS = [("flash.fwd", 0, 1), ("router", 1, 1.5), ("sort", 1.5, 2.5),
            ("gmm.fwd", 2.5, 7.5), ("combine", 7.5, 8), ("norm", 8, 8.25),
            ("head", 8.25, 12), ("gmm.bwd", 12, 22), ("scatter", 22, 23),
            ("flash.bwd", 23, 25.5), ("copy", 26, 27), ("silu", 27, 27.5),
            ("adamw", 27.5, 29.5)]
DRAWN = {
    "chips": [{"plane": "/device:TPU:0", "program": "jit_step",
               "steps": [[0, 30 * MS], [30 * MS, 60 * MS]],
               "ops": [[name, int((at + a) * MS), int((at + b) * MS)]
                       for at in (0, 30) for name, a, b in STEP_OPS]}],
    "host": [], "lines": {},
}
FWD = "jit(step)/shard_map/jvp()/while/body/closed_call"
BWD = "jit(step)/shard_map/transpose(jvp())/while/body/closed_call"
SCOPES = {
    "flash.fwd": f"{FWD}/attn/attn_core/pallas_call",
    "flash.bwd": f"{BWD}/attn/attn_core/pallas_call",
    "router": f"{FWD}/moe/moe_router/dot_general",
    "sort": f"{FWD}/moe/moe_dispatch/sort",
    # as the TPU compiler names what it makes of `lax.ragged_dot`: no path
    "gmm.fwd": "ragged-dot-none",
    "gmm.bwd": "ragged-dot-none",
    "silu": f"{BWD}/moe/moe_experts/checkpoint/mul",
    "combine": f"{FWD}/moe/moe_combine/gather",
    "scatter": f"{BWD}/moe/moe_dispatch/scatter-add",
    "norm": f"{FWD}/moe/checkpoint/rsqrt",
    "head": "jit(step)/shard_map/jvp(head_loss)/dot_general",
    "adamw": "jit(step)/shard_map/optimizer/optimizer_update/add",
}


def _record(samples_per_step=2):
    return {"workload": CELL, "scopes": SCOPES, "samples_per_step": samples_per_step,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_drawn_moe_times():
    record = _record()
    assert expert_ffn_ms.read(record, DRAWN) == pytest.approx(5 + 10 + 0.5)
    assert moe_dispatch_ms.read(record, DRAWN) == pytest.approx(0.5 + 1 + 0.5 + 1)
    assert moe_ms.read(record, DRAWN) == pytest.approx(15.5 + 3 + 0.25)
    assert flash_core_ms.read(record, DRAWN) == pytest.approx(1 + 2.5)
    # the parts lie inside the whole, and what `moe` holds outside its four
    # sub-scopes is the norm
    rest = (moe_ms.read(record, DRAWN) - expert_ffn_ms.read(record, DRAWN)
            - moe_dispatch_ms.read(record, DRAWN))
    assert rest == pytest.approx(0.25)


def test_drawn_shares_of_the_peak():
    """At the real widths: 2 sequences of 4,096 tokens a step, one layer."""
    record = _record()
    peak = harness.load_peaks("TPU v5 lite")["bf16_flops"]
    experts = 8192 * (3 * 8 * 3 * 2 * 2048 * 1024)
    assert expert_matmul_peak_pct.read(record, DRAWN) == pytest.approx(
        100 * experts / 15.5e-3 / peak)
    core = 2 * 6 * (2 * 4096 * 4096 * 2048 / 2)
    assert flash_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * core / 3.5e-3 / peak)
    # drawn to be possible: under the peak, over nothing
    assert 0 < expert_matmul_peak_pct.read(record, DRAWN) <= 100
    assert 0 < flash_roofline_pct.read(record, DRAWN) <= 100


READERS = (moe_ms, expert_ffn_ms, moe_dispatch_ms, flash_core_ms,
           flash_roofline_pct, expert_matmul_peak_pct)


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__.split(".")[-1])
def test_readers_find_nothing_without_a_trace_or_a_scope_table(reader):
    assert reader.read(_record(), None) is None
    assert reader.read(_record(), {"chips": [], "host": [], "lines": {}}) is None
    for scopes in (None, {}):
        assert reader.read({**_record(), "scopes": scopes}, DRAWN) is None
    assert reader.read({"workload": CELL}, DRAWN) is None


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__.split(".")[-1])
def test_a_program_without_the_scope_reads_nothing_run(reader):
    """A scope table that names no `moe` and no `attn_core` (a step of the
    other families): nothing ran under them, 0, and no share of any peak."""
    record = {**_record(), "scopes": {"head": SCOPES["head"]}}
    assert reader.read(record, DRAWN) == 0.0


def test_the_traced_line_holds_the_six_new_metrics():
    manifest = mf.load()
    record = {**_record(), "traced": True, **drawn_setup(), "chips": 1,
              "window": {"compiles": 0, "t_done": [1.0, 1.1, 1.2, 1.3],
                         "spans": [["bench.input", 1.0, 1.001]]},
              "program_memory": {"total_bytes": 12_400_000_000},
              "memory_stats_peak_bytes": 1, "correct": True, "attempted": 20,
              "failed": 0}
    line = end_to_end.result_line(record, DRAWN, manifest)
    mine = {x["name"] for x in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(line["metrics"]) == mine
    assert {r.__name__.split(".")[-1] for r in READERS} <= mine
    assert "attention_core_ms" not in mine  # it lists the bert_base cells alone
    # head and optimizer, near half of the step, are read here since PR 38
    assert line["metrics"]["head_loss_ms"]["value"] == pytest.approx(3.75)
    assert line["metrics"]["optimizer_ms"]["value"] == pytest.approx(2.0)
    units = {x["name"]: x for x in manifest["per_layer"]}
    for reader in READERS:
        entry = units[reader.__name__.split(".")[-1]]
        # the cell the reader came with stands first in its list; later
        # cells joined `moe_ms`', `expert_ffn_ms`' and `moe_dispatch_ms`'
        assert entry["workloads"][0] == CELL and entry["moves"] == "step_ms_p50"
        assert entry["source"] == "device_trace"
    assert line["metrics"]["flash_roofline_pct"]["unit"] == "%"
    assert mf.check_result_line(line, manifest, CELL, True) == []
