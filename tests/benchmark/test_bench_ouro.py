"""The Ouro family, its configuration and its five readers (PR 48): the whole
of `harness.measure` at tiny size on the CPU mesh, the parameter and
operation counts against the initialised tree and sums made by hand (a layer
counted once a loop step, and so the head), the batches, the readers against
a drawn trace, and the configuration file against the catalog's numbers.

These tests find the cell and its entries by name, wherever later cells put
them: no position in the manifest is pinned."""

import copy
import json
import time

import jax
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.families import ouro
from benchmark.launchers.none import OneProcess
from benchmark.layer_metrics import (loop_core_ms, loop_core_roofline_pct,
                                     loop_exit_ms, loop_ffn_ms, loop_norm_ms)
from drawn_setup import child_marks, drawn_setup

CELL = "ouro_2_6b.ssgd_loop4_4k_1chip"
NAME = "ouro_2_6b"
# the five metrics the cell brought, and the older lists it joined: the step's
# parts, which tests/benchmark/test_bench_setup.py wants of every transformer
# cell
MINE = (("loop_core_ms", "ms", "lower", "Kernels"),
        ("loop_core_roofline_pct", "%", "higher", "Kernels"),
        ("loop_ffn_ms", "ms", "lower", "Model"),
        ("loop_norm_ms", "ms", "lower", "Model"),
        ("loop_exit_ms", "ms", "lower", "Model"))
JOINED = ("optimizer_ms", "head_loss_ms")

# the model in small (tests/test_ouro.py); the kernel in interpret mode by a
# key of the configuration
TINY = dict(hidden_size=64, intermediate_size=96, head_dim=16,
            num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=2,
            vocab_size=320, sequence_length=64, flash_blocks=[32, 32],
            flash_interpret=True)  # 320: no layer's width

# ByteDance/Ouro-2.6B's config.json as the catalog has it
CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152}


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
    assert cell == {**cell, "config": NAME, "traffic": "ssgd_loop4_4k_1chip",
                    "chips": 1}
    for word in ("4,096", "4 loop steps", "32 applications", "shared weights",
                 "49,152", "exit gate"):
        assert word in cell["why"], word
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["reduced"] == ["num_hidden_layers"]
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == [
        {"name": name, "unit": unit, "better": better, "source": "device_trace",
         "layer": layer, "moves": "step_ms_p50", "workloads": [CELL]}
        for name, unit, better, layer in MINE]
    assert sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", []) and m not in mine) == sorted(JOINED)
    # nine cells or more, and the four-chip places are still two at the most
    assert len(manifest["workloads"]) >= 9
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] == [
        "bert_base.ssgd_kfrun_4chip"]


def test_the_configuration_is_the_catalogs_but_for_its_depth():
    manifest = mf.load()
    config = _real()
    differs = sorted(k for k, v in CATALOG.items() if config.get(k) != v)
    assert differs == config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8
    assert config["published"] == {"num_hidden_layers": 48}
    # the widths, the heads, the vocabulary and the loop are the source's
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["vocab_size"],
            config["total_ut_steps"], config["rope_theta"]) == (
        2048, 16, 16, 128, 5632, 49152, 4, 1000000)
    assert ouro.layer_types(config) == ["full_attention"] * 8
    (entry,) = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    assert "layers 8 to 47" in config["deployment"]
    assert "first stage takes the last stage's normed output back" in (
        config["deployment"])
    assert len(config["assumed"]) >= 10
    for word in ("input_layernorm_2", "post_attention_layernorm_2",
                 "end of every loop step", "early_exit_gate", "with a bias",
                 "first stage", "exit_entropy_coef", "second-stage",
                 "rotate-half", "normal(0, 0.02)", "b_g", "sequence_length",
                 "uniform", "float32 norm statistics", "run again",
                 "early_exit_threshold"):
        assert any(word in line for line in config["assumed"]), word
    assert config["sequence_length"] == 4096
    assert config["exit_entropy_coef"] == 0.05
    assert config["flash_blocks"] == [512, 512]
    assert config["recomputed_layer_types"] == ["full_attention"]
    assert (config["param_dtype"], config["compute_dtype"], config["head_dtype"]) == (
        "float32", "bfloat16", "float32")
    traffic = mf.cell(manifest, CELL)["traffic"]
    assert (traffic["per_chip_batch"], traffic["pool"], traffic["mesh"]) == (
        1, 8, {"dp": 1})
    assert (traffic["launcher"], traffic["step"], traffic["placement"]) == (
        "none", "ssgd", "shard_batch")
    # ISSUE 48's: a constant rate from the initial parameters
    assert traffic["optimizer"] == {"name": "adamw", "learning_rate": 0.0003}


def test_the_cut_holds_612_438_017_parameters():
    """ISSUE 48's count, by `eval_shape`: 51,388,416 a layer (16,777,216 in
    attention, 34,603,008 in the feed-forward, 8,192 in four norms), 2 x
    100,663,296 in embedding and head, 2,048 in the final norm and 2,049 in
    the exit gate; 9.80e9 bytes at 16 a parameter."""
    state = jax.eval_shape(lambda: ouro.init(_real(), 0))

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    layers = state["layers"]
    assert {leaf.shape[0] for leaf in jax.tree.leaves(layers)} == {8}
    assert size(layers) == 8 * 51_388_416
    assert size({k: layers[k] for k in ("wq", "wk", "wv", "wo")}) == 8 * 16_777_216
    assert size({k: layers[k] for k in ("w_gate", "w_up", "w_down")}) == 8 * 34_603_008
    assert sorted(k for k in layers if k.startswith("ln")) == [
        "ln1_post_scale", "ln1_scale", "ln2_post_scale", "ln2_scale"]
    assert layers["wq"].shape == layers["wk"].shape == (8, 2048, 2048)
    assert layers["w_down"].shape == (8, 5632, 2048)
    assert size(state["embed"]) == size(state["lm_head"]) == 100_663_296
    assert state["exit_gate_w"].shape == (2048, 1) and state["exit_gate_b"].shape == ()
    assert "pos_embed" not in state
    assert size(state) == 612_438_017
    assert 9.79e9 < 16 * size(state) < 9.80e9
    mc = ouro.model_config(_real())
    assert (mc.loop_steps, mc.post_norms, mc.exit_entropy_coef) == (4, True, 0.05)
    assert (mc.n_heads, mc.kv_heads, mc.head_dim, mc.d_ff) == (16, 16, 128, 5632)
    assert (mc.positions, mc.rope_theta, mc.norm_eps, mc.tied_head) == (
        "rope", 1e6, 1e-6, False)
    assert [(kind.layer_remat, n) for kind, n in mc.stacks] == [(True, 8)]
    assert mc.attn_core == "flash" and mc.flash_blocks == (512, 512)


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("rope_scaling", {"type": "yarn"}), ("use_sliding_window", True),
    ("sliding_window", 4096), ("num_key_value_heads", 2),
    ("early_exit_threshold", 0.9), ("layer_types", ["sliding_attention"] * 48),
    ("layer_types", ["full_attention"])])
def test_the_family_refuses_a_layer_it_does_not_run(key, value):
    with pytest.raises(ValueError, match="as published"):
        ouro.model_config(_tiny_config(**{key: value}))


# --- operation and byte counts, by hand --------------------------------------

def test_flops_per_sample_count_a_layer_and_the_head_once_a_loop_step():
    """Per token and forward pass at the tests' size: a layer (four 64 x 64
    projections, three 64 x 96 of the feed-forward) twice a loop step, the
    head 320 x 64 once a loop step, the gate's 64 on three of the four; 2
    operations a multiply-add, x 3 for forward and backward; and the 8
    cores."""
    config = _tiny_config()
    layer = 4 * 64 * 64 + 3 * 64 * 96
    assert ouro.layer_params_per_token(config) == layer
    params = 4 * 2 * layer + 4 * 320 * 64 + 3 * 64
    assert ouro.matmul_params_per_token(config) == params
    assert ouro.core_applications(config) == 8
    core = 6 * 2 * (64 * 64 / 2) * 4 * 16
    assert ouro.core_flops_per_sample(config) == core
    assert ouro.core_bytes_per_sample(config) == 12 * 64 * 64 * 2
    assert ouro.flops_per_sample(config) == 3 * 2 * params * 64 + 8 * core
    # one loop step reads a quarter: the count is of the applications
    once = ouro.flops_per_sample({**config, "total_ut_steps": 1})
    assert once == 3 * 2 * (2 * layer + 320 * 64) * 64 + 2 * core
    assert ouro.flops_per_sample(config) == pytest.approx(4 * once, rel=1e-3)
    real = _real()
    # the issue's arithmetic: 51,380,224 a layer application, 100,663,296 a
    # head pass, 2,046,828,544 applied a token but for the fourth gate's
    # 2,048, which nothing reads; 50.3 T and 32 cores of 0.206 T: 57 T a step
    assert ouro.layer_params_per_token(real) == 51_380_224
    assert ouro.matmul_params_per_token(real) == 2_046_828_544 - 2_048
    assert ouro.core_flops_per_sample(real) == 6 * 2 * 4096 * 4096 / 2 * 2048
    assert 32 * ouro.core_flops_per_sample(real) == pytest.approx(6.6e12, rel=1e-2)
    assert ouro.flops_per_sample(real) == pytest.approx(56.9e12, rel=1e-3)
    # on the v5e the operations bound a core: 1.05 ms against 0.25
    peaks = harness.load_peaks("TPU v5 lite")
    t_flops = ouro.core_flops_per_sample(real) / peaks["bf16_flops"]
    t_bytes = ouro.core_bytes_per_sample(real) / peaks["hbm_bytes_per_s"]
    assert t_flops == pytest.approx(1.047e-3, rel=1e-2) and t_bytes < t_flops / 4


def test_the_multiplying_parameters_are_the_initialised_trees():
    """Every matrix of the initialised tree but the embedding (a lookup)
    multiplies every token: the layers' and the head's four times a forward
    pass, the gate's column three times."""
    real = _real()
    state = jax.eval_shape(lambda: ouro.init(real, 0))
    matrices = sum(x.size for x in jax.tree.leaves(state["layers"]) if x.ndim == 3)
    assert ouro.matmul_params_per_token(real) == (
        4 * matrices + 4 * state["lm_head"].size + 3 * state["exit_gate_w"].size)


def test_host_batches_come_from_the_seed_uniform_over_the_vocabulary():
    config = _tiny_config(sequence_length=4096)
    a = ouro.host_batch(config, 2**31 + 11, 3, 2)
    b = ouro.host_batch(config, 2**31 + 11, 3, 2)
    c = ouro.host_batch(config, 2**31 + 12, 3, 2)
    assert a.shape == (2, 4097) and a.dtype == np.int32  # S + 1 ids
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < 320
    counts = np.bincount(a.ravel(), minlength=320)
    assert counts.min() > 5 and counts.max() < 64 and 150 < np.median(a) < 170
    real = ouro.host_batch(_real(), 2**31 + 11, 0, 1)
    assert real.shape == (1, 4097) and 40_000 < real.max() < 49152
    assert np.bincount(real.ravel(), minlength=49152).max() < 8


# --- the program against the reference --------------------------------------

def test_the_reference_computes_in_blocks_and_loop_steps_what_it_computes_at_once():
    from benchmark.reference import ouro as reference

    config = _tiny_config(compute_dtype="float32")
    state = ouro.init(config, 3)
    sample = ouro.host_batch(config, 3, 0, 1)
    whole = dict(ouro._hyper(config), query_block=64)
    at_once = reference.loss_and_grads(state, sample, **whole)
    in_blocks = reference.loss_and_grads(state, sample,
                                         **{**whole, "query_block": 16})
    assert float(at_once[0]) == pytest.approx(float(in_blocks[0]), rel=1e-6)
    assert harness.relative_error(in_blocks[1], at_once[1]) <= 1e-5
    # a loop step at a time (`loss_and_grads`) is autodiff of the one function
    import functools

    with jax.default_matmul_precision("highest"):
        whole_loss, whole_grads = jax.jit(jax.value_and_grad(
            functools.partial(reference.loss, **whole)))(state, sample)
    assert float(whole_loss) == pytest.approx(float(at_once[0]), rel=1e-6)
    assert jax.tree.structure(whole_grads) == jax.tree.structure(at_once[1])
    assert harness.relative_error(at_once[1], whole_grads) <= 1e-6
    # and the program is the reference's mathematics at this size too
    loss, grads = ouro.program_loss_and_grads(config)(state, sample)
    assert float(loss) == pytest.approx(float(at_once[0]), rel=1e-5)
    assert harness.relative_error(grads, at_once[1]) <= 1e-4


def test_the_reference_imports_nothing_of_the_program():
    import benchmark.reference.ouro as reference

    with open(reference.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "kungfu_tpu" in line]
    assert "pallas" not in text and "custom_vjp" not in text
    # the loops over the loop steps and the layers are Python's
    assert "lax.scan" not in text and "for stacked in stacks" in text
    assert 'default_matmul_precision("highest")' in text
    assert ouro.REFERENCE_SAMPLES == 1


def test_the_cells_program_holds_to_its_declared_precision():
    config = _tiny_config()
    state = jax.eval_shape(lambda: ouro.init(config, 0))
    sample = ouro.host_batch(config, 0, 0, ouro.REFERENCE_SAMPLES)
    traced = ouro.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, ouro.head_width(config),
                                    traced.jaxpr, state, state) == []
    low = _tiny_config(param_dtype="bfloat16")
    assert harness.precision_faults(low, ouro.head_width(low),
                                    traced.jaxpr, state, state)


def test_the_real_program_holds_to_its_declared_precision():
    """At the published widths, from shapes alone: no matmul or reduction
    over the head's 49,152 rows is in bfloat16, in any of the four passes or
    where a pass is run again, and no other array of the step has that
    width."""
    config = _real()
    state = jax.eval_shape(lambda: ouro.init(config, 0))
    sample = ouro.host_batch(config, 0, 0, ouro.REFERENCE_SAMPLES)
    traced = ouro.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, ouro.head_width(config),
                                    traced.jaxpr, state, state) == []
    wide = [eqn for eqn in harness.eqns_of(traced.jaxpr.jaxpr)
            if eqn.primitive.name == "dot_general" and any(
                49152 in getattr(v.aval, "shape", ()) for v in eqn.outvars)]
    assert wide  # the check above did read the head's products


# --- the whole of measure ----------------------------------------------------

@pytest.fixture(scope="module")
def events():
    return harness.EventCounter()


def test_measure_at_tiny_size_on_two_cpu_devices(events):
    """State, pool, first step, warm-up, probe, window, checks and the
    reference, on a dp = 2 mesh of virtual CPU devices: the looped model
    through `make_mesh` -> `synchronous_sgd(adamw)` -> `make_train_step` as
    every cell goes."""
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
    assert record["flops_per_sample"] == ouro.flops_per_sample(cell["config"])
    json.dumps(record)
    assert all(v > 0 for v in end_to_end.values(record).values())
    with pytest.raises(RuntimeError, match="chip runs only"):
        end_to_end.result_line(record, None, m)


# --- the readers on a drawn trace ---------------------------------------

MS = 8_000_000  # a unit of the drawing below, in ns: 8 ms
# Two steps of 40 units on one chip, each alike (a loop step drawn once):
#   qkv [0, 2)  core.fwd [2, 4)  wo [4, 5)  norm2 [5, 5.5)  ffn.up [5.5, 8)
#   ffn.down [8, 9)  norm4 [9, 9.5)  loop.norm [9.5, 10)  head [10, 12)
#   gate [12, 12.5)  shares [12.5, 13)  shares.bwd [13, 13.5)
#   head.again [13.5, 15.5)  head.bwd [15.5, 19)  loop.norm.bwd [19, 19.5)
#   norm4.bwd [19.5, 20)  ffn.again [20, 23)  ffn.bwd [23, 28)
#   norm2.bwd [28, 28.5)  core.bwd [28.5, 33)  qkv.bwd [33, 36)  adamw [36, 39)
#   copy [39, 39.5) (no scope: unattributed)
STEP_OPS = [("qkv", 0, 2), ("core.fwd", 2, 4), ("wo", 4, 5), ("norm2", 5, 5.5),
            ("ffn.up", 5.5, 8), ("ffn.down", 8, 9), ("norm4", 9, 9.5),
            ("loop.norm", 9.5, 10), ("head", 10, 12), ("gate", 12, 12.5),
            ("shares", 12.5, 13), ("shares.bwd", 13, 13.5),
            ("head.again", 13.5, 15.5), ("head.bwd", 15.5, 19),
            ("loop.norm.bwd", 19, 19.5), ("norm4.bwd", 19.5, 20),
            ("ffn.again", 20, 23), ("ffn.bwd", 23, 28), ("norm2.bwd", 28, 28.5),
            ("core.bwd", 28.5, 33), ("qkv.bwd", 33, 36), ("adamw", 36, 39),
            ("copy", 39, 39.5)]
DRAWN = {
    "chips": [{"plane": "/device:TPU:0", "program": "jit_step",
               "steps": [[0, 40 * MS], [40 * MS, 80 * MS]],
               "ops": [[name, int((at + a) * MS), int((at + b) * MS)]
                       for at in (0, 40) for name, a, b in STEP_OPS]}],
    "host": [], "lines": {},
}
LOOP = "jit(step)/shard_map/jvp()/while/body"
FWD = f"{LOOP}/while/body/closed_call"
BACK = "jit(step)/shard_map/transpose(jvp())/while/body"
BWD = f"{BACK}/while/body/closed_call/checkpoint"
SCOPES = {
    "qkv": f"{FWD}/attn/dot_general",
    "core.fwd": f"{FWD}/attn/attn_full/attn_core/pallas_call",
    "wo": f"{FWD}/attn/dot_general",
    "norm2": f"{FWD}/attn/post_norm/rsqrt",
    "ffn.up": f"{FWD}/ffn/dot_general",
    "ffn.down": f"{FWD}/ffn/dot_general",
    "norm4": f"{FWD}/ffn/post_norm/rsqrt",
    "loop.norm": f"{LOOP}/loop_norm/rsqrt",
    "head": f"{LOOP}/head_loss/dot_general",
    "gate": "jit(step)/shard_map/jvp(exit_gate)/dot_general",
    "shares": "jit(step)/shard_map/jvp(exit_gate)/exp",
    "shares.bwd": "jit(step)/shard_map/transpose(jvp(exit_gate))/mul",
    "head.again": f"{BACK}/head_loss/dot_general",
    "head.bwd": f"{BACK}/head_loss/dot_general",
    "loop.norm.bwd": f"{BACK}/loop_norm/mul",
    "norm4.bwd": f"{BWD}/ffn/post_norm/mul",
    "ffn.again": f"{BWD}/ffn/dot_general",
    "ffn.bwd": f"{BWD}/ffn/dot_general",
    "norm2.bwd": f"{BWD}/attn/post_norm/mul",
    "core.bwd": f"{BWD}/attn/attn_full/attn_core/pallas_call",
    "qkv.bwd": f"{BWD}/attn/dot_general",
    "adamw": "jit(step)/shard_map/optimizer/optimizer_update/add",
}


def _record(samples_per_step=1):
    return {"workload": CELL, "scopes": SCOPES, "samples_per_step": samples_per_step,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_drawn_times():
    record = _record()
    assert loop_core_ms.read(record, DRAWN) == pytest.approx(8 * (2 + 4.5))
    # `ffn` and not the norm behind it, which is the norms' reader's
    assert loop_ffn_ms.read(record, DRAWN) == pytest.approx(8 * (2.5 + 1 + 3 + 5))
    # the four second norms drawn, forward and backward, and the loop's own
    assert loop_norm_ms.read(record, DRAWN) == pytest.approx(8 * (4 * 0.5 + 2 * 0.5))
    assert loop_exit_ms.read(record, DRAWN) == pytest.approx(8 * 1.5)


def test_drawn_share_of_the_roofline():
    """At the real widths: one sequence of 4,096 tokens a step, 32 cores
    bound by their operations."""
    record = _record()
    peaks = harness.load_peaks("TPU v5 lite")
    cores = 32 * 6 * 2 * (4096 * 4096 / 2) * 16 * 128 / peaks["bf16_flops"]
    assert loop_core_roofline_pct.read(record, DRAWN) == pytest.approx(
        100 * cores / 52e-3)
    # a step of two sequences has twice the work in the same drawn time
    assert loop_core_roofline_pct.read(_record(2), DRAWN) == pytest.approx(
        2 * loop_core_roofline_pct.read(record, DRAWN))
    assert 60 < loop_core_roofline_pct.read(record, DRAWN) < 70


def test_the_roofline_counts_nothing_a_core_might_skip():
    """The causal half, each of the six products once, 32 applications; every
    array once each way: no recomputation."""
    real = _real()
    assert ouro.core_applications(real) == 32
    assert ouro.core_flops_per_sample(real) == 6 * 2 * 4096 * 4096 / 2 * 16 * 128
    assert ouro.core_bytes_per_sample(real) == 12 * 4096 * 2048 * 2


READERS = (loop_core_ms, loop_core_roofline_pct, loop_ffn_ms, loop_norm_ms,
           loop_exit_ms)


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
    record = {**_record(), "scopes": {"adamw": SCOPES["adamw"]}}
    assert reader.read(record, DRAWN) == 0.0


def test_the_traced_line_holds_exactly_the_cells_metrics():
    manifest = mf.load()
    record = {**_record(), "traced": True, **drawn_setup(), "chips": 1,
              "window": {"compiles": 0, "t_done": [1.0, 1.4, 1.8, 2.2],
                         "spans": [["bench.input", 1.0, 1.001]]},
              "program_memory": {"total_bytes": 15_150_000_000},
              "memory_stats_peak_bytes": 1, "correct": True, "attempted": 20,
              "failed": 0}
    line = end_to_end.result_line(record, DRAWN, manifest)
    mine = {x["name"] for x in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(line["metrics"]) == mine
    assert {r.__name__.split(".")[-1] for r in READERS} <= mine
    assert set(JOINED) <= mine
    assert not {"full_core_ms", "moe_share_ms", "flash_roofline_pct",
                "flash_core_ms", "moe_ms", "gdn_core_ms", "ssm_core_ms",
                "nope_core_ms", "moe_relu2_ms", "mla_core_ms", "mtp_ms"} & mine
    assert line["metrics"]["optimizer_ms"]["value"] == pytest.approx(8 * 3.0)
    # all of the passes, and where a pass is run again
    assert line["metrics"]["head_loss_ms"]["value"] == pytest.approx(8 * (2 + 2 + 3.5))
    assert line["metrics"]["loop_core_ms"]["value"] == pytest.approx(8 * 6.5)
    assert line["metrics"]["loop_core_roofline_pct"]["unit"] == "%"
    assert mf.check_result_line(line, manifest, CELL, traced=True) == []
