"""The traffic of the Keye and SmallThinker cells since PR 67: AdamW under a
warm-up of the rate (`benchmark/optimizers/adamw_warmup.py`), and the state
the timed steps train from, which the configuration's file names
(`timed_state`) and `harness.measure` reads in one place, `harness.timed_state`, while the
reference check keeps meeting `--seed`'s weights and sample."""

import time
import types

import jax
import numpy as np
import pytest

from benchmark import harness, manifest as mf
from benchmark.launchers.none import OneProcess
from benchmark.optimizers import adamw, adamw_warmup
from drawn_setup import child_marks
from test_bench_keye_vl2 import TINY as KEYE_TINY
from test_bench_smallthinker import TINY as SMALLTHINKER_TINY

SPEC = {"name": "adamw_warmup", "learning_rate": 0.0003, "warmup_steps": 2000}
FIXED = {"keye_vl_2_0_30b_a3b.ssgd_dsa_1chip": KEYE_TINY,
         "smallthinker_21b_a3b.ssgd_swa_nope_1chip": SMALLTHINKER_TINY}
CELLS = [w["name"] for w in mf.load()["workloads"]]
FREE = [name for name in CELLS if name not in FIXED]


@pytest.mark.parametrize("step,rate", [(0, 0.0), (1, 1.5e-7), (1000, 1.5e-4),
                                       (2000, 3e-4), (2001, 3e-4), (10**6, 3e-4)])
def test_the_rate_warms_up_linearly_and_stays(step, rate):
    assert float(adamw_warmup.schedule(SPEC)(step)) == pytest.approx(rate, rel=1e-5)


def test_a_spec_without_warmup_steps_is_refused():
    with pytest.raises(KeyError, match="warmup_steps"):
        adamw_warmup.make({"name": "adamw_warmup", "learning_rate": 0.0003})


def test_the_optimizer_is_adamw_at_the_schedules_rate():
    """Step 0 moves nothing (the rate is 0, the decay with it); a later
    step is `adamw`'s at a constant rate, scaled by the schedule's."""
    params = {"w": jax.numpy.linspace(-1.0, 1.0, 8)}
    grads = {"w": jax.numpy.linspace(0.5, -0.25, 8)}
    warm, plain = adamw_warmup.make(SPEC), adamw.make({"learning_rate": 0.0003})
    warm_state, plain_state = warm.init(params), plain.init(params)
    for step in range(3):
        moved, warm_state = warm.update(grads, warm_state, params)
        full, plain_state = plain.update(grads, plain_state, params)
        np.testing.assert_allclose(moved["w"], full["w"] * step / 2000, rtol=1e-5)


@pytest.mark.parametrize("workload", sorted(FIXED))
def test_the_two_cells_files_state_the_traffic(workload):
    """The traffic file names the warm-up; the configuration's file names
    the state's seed, the rule that chose it and what the rule read: of
    the sixteen sums the one nearest the balanced sum, ties to the lower
    seed, and that seed's rows a layer."""
    cell = mf.cell(mf.load(), workload)
    assert cell["traffic"]["optimizer"] == SPEC
    for word in ("warm-up", "2,000 steps", "timed_state", "--seed"):
        assert word in cell["traffic"]["what"], word
    timed, config = cell["config"]["timed_state"], cell["config"]
    rule = timed["rule"]
    assert sorted(timed) == ["held_rows", "pool", "rule", "seed"]
    assert rule["seeds"] == 16 == len(rule["sums"]) and rule["batch"] == 0
    assert rule["batches"] == len(rule["batch_sums"]) >= 256
    assert rule["keep"] == cell["traffic"]["pool"] == len(timed["pool"])
    family = harness.family_of(config)
    layers = config["num_hidden_layers"]  # every layer an expert layer
    tokens = config["sequence_length"] * cell["traffic"]["per_chip_batch"]
    balanced = round(layers * tokens * family.expected_expert_passes(config))
    assert rule["balanced_sum"] == balanced

    def nearest(sums, keep):
        return sorted(sorted(range(len(sums)),
                             key=lambda i: (abs(sums[i] - balanced), i))[:keep])

    assert [timed["seed"]] == nearest(rule["sums"], 1)
    assert timed["pool"] == nearest(rule["batch_sums"], rule["keep"])
    # batch 0 of the seed's stream is what the sixteen readings read
    assert rule["batch_sums"][0] == rule["sums"][timed["seed"]]
    assert [sum(rows) for rows in timed["held_rows"]] == [
        rule["batch_sums"][i] for i in timed["pool"]]
    for rows in timed["held_rows"]:
        assert len(rows) == layers and all(r > 0 for r in rows)
        assert abs(sum(rows) - balanced) <= 0.02 * balanced
    assert harness.timed_state(config, cell["traffic"], 5) == (
        timed["seed"], [(timed["seed"], i) for i in timed["pool"]])
    why = next(w["why"] for w in mf.load()["workloads"] if w["name"] == workload)
    for word in ("warm-up", "state fixed"):
        assert word in why, word


@pytest.mark.parametrize("workload", FREE)
def test_a_configuration_without_the_key_takes_the_runs_seed(workload):
    cell = mf.cell(mf.load(), workload)
    assert "timed_state" not in cell["config"]
    assert cell["traffic"]["optimizer"]["name"] in ("adamw", "sgd")
    for seed in (0, 3, 2**31 + 7):
        assert harness.timed_state(cell["config"], cell["traffic"], seed) == (
            seed, [(seed, i) for i in range(cell["traffic"]["pool"])])


@pytest.fixture(scope="module")
def events():
    return harness.EventCounter()


@pytest.mark.parametrize("workload", sorted(FIXED))
def test_the_timed_loop_trains_from_the_files_state_under_any_seed(
        workload, events, monkeypatch):
    """`measure` makes one state, the family's at `timed_state.seed`, byte
    for byte, whatever `--seed`; it hands `--seed` to the reference check
    (whose own test is below), and the pool is the file's batches of that
    seed's stream. The
    warm-up's first step moves nothing: on a pool of one batch the first
    two losses are equal."""
    from kungfu_tpu.parallel import make_mesh

    cell = mf.cell(mf.load(), workload)
    cell["config"].update(FIXED[workload])
    cell["config"]["timed_state"] = {"seed": 11, "pool": [5]}
    cell["traffic"].update(pool=1)
    family = harness.family_of(cell["config"])
    want = harness.params_digest(family.init(cell["config"], 11))
    made, pools, checked = [], [], []
    init, host_batch = family.init, family.host_batch

    def noting_init(config, seed):
        state = init(config, seed)
        made.append((seed, harness.params_digest(state)))
        return state

    def noting_batch(config, seed, i, n):
        pools.append((seed, i))
        return host_batch(config, seed, i, n)

    def reference_check(family, config, seed, state, opt_state):
        checked.append(seed)
        return {"loss_error": 0.0, "loss_rtol": 1.0, "grad_error": 0.0,
                "grad_rtol": 1.0, "precision_faults": []}

    monkeypatch.setattr(family, "init", noting_init)
    monkeypatch.setattr(family, "host_batch", noting_batch)
    monkeypatch.setattr(harness, "reference_check", reference_check)
    seed = 2**31 + 7
    for any_seed in (3, seed):
        assert harness.timed_state(cell["config"], cell["traffic"], any_seed) == (
            11, [(11, 5)])
    with pytest.raises(ValueError, match="names 1 batches"):
        harness.timed_state(cell["config"], {"pool": 8}, 3)
    record = harness.measure(
        cell, make_mesh({"dp": 1}, devices=jax.devices()[:1]), OneProcess(),
        {"bf16_flops": 197e12}, seed=seed, seconds=0.05, trace_dir=None,
        events=events, t_command=time.time(), marks=child_marks())
    assert made == [(11, want)] and checked == [seed] and pools == [(11, 5)]
    assert record["seed"] == seed and record["timed_state_seed"] == 11
    assert record["losses_before"][0] == record["losses_before"][1]
    assert record["correct"], record["checks"]


def test_the_reference_check_meets_the_runs_seed():
    """`reference_check` is as it was: its state is the family's at `--seed`
    and its sample `--seed`'s at SAMPLE_INDEX, whatever the configuration
    says of the timed state, so two seeds give it two states and two
    samples."""
    seen = []

    def program_loss_and_grads(config):
        return jax.jit(lambda state, sample: (state["w"].sum() + sample.sum(),
                                              {"w": state["w"] * 2}))

    def reference_loss_and_grads(config, state, sample):
        seen.append((harness.params_digest(state), harness.params_digest(sample)))
        return state["w"].sum() + sample.sum(), {"w": state["w"] * 2}

    family = types.SimpleNamespace(
        init=lambda config, seed: {"w": jax.random.normal(jax.random.PRNGKey(seed), (4,))},
        host_batch=lambda config, seed, i, n: np.random.default_rng(
            [seed, i]).random((n, 3), np.float32),
        program_loss_and_grads=program_loss_and_grads,
        reference_loss_and_grads=reference_loss_and_grads,
        head_width=lambda config: 7, REFERENCE_SAMPLES=2, LOSS_RTOL=1e-6,
        GRAD_RTOL=1e-6)
    config = {"timed_state": {"seed": 11}, "param_dtype": "float32",
              "head_dtype": "float32"}
    shapes = {"w": jax.ShapeDtypeStruct((4,), "float32")}
    for seed in (3, 2**31 + 7):
        found = harness.reference_check(family, config, seed, shapes, shapes)
        assert found["grad_error"] == 0 and found["precision_faults"] == []
        state, sample = family.init(config, seed), family.host_batch(
            config, seed, harness.SAMPLE_INDEX, 2)
        assert seen[-1] == (harness.params_digest(state),
                            harness.params_digest(sample))
    assert seen[0][0] != seen[1][0] and seen[0][1] != seen[1][1]
