"""BENCHMARK.json's self-check: the manifest in the tree is sound, and each
rule of the contract that can be checked without a chip refuses its fault —
first of all the one PR 22 was refused for."""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest as mf


@pytest.fixture(scope="module")
def sound():
    return mf.load()


def test_the_manifest_in_the_tree_is_sound(sound):
    assert mf.check(sound) == []


def test_every_end_to_end_metric_is_reported_in_every_cell(sound):
    cells = [w["name"] for w in sound["workloads"]]
    for m in sound["end_to_end"]:
        assert mf.reported_in(m, sound) == cells, m["name"]
    e2e = {m["name"] for m in sound["end_to_end"]}
    for m in sound["per_layer"]:
        assert m["moves"] in e2e, m["name"]


def test_pr22_fault_is_refused(sound):
    """A per-layer metric reported in a cell where the metric it moves is
    not: the driver's own words for PR 22."""
    m = copy.deepcopy(sound)
    moved = m["per_layer"][0]["moves"]
    first, *rest = [w["name"] for w in m["workloads"]]
    for e in m["end_to_end"]:
        if e["name"] == moved:
            e["workloads"] = rest  # no longer reported in the first cell
    faults = mf.check(m)
    assert any(
        f"is reported on workload {first}, where {moved}, which it should "
        "move, is not" in f for f in faults), faults


def _set(path, value):
    def edit(m):
        at = m
        for key in path[:-1]:
            at = at[key]
        at[path[-1]] = value
    return edit


def _del(path):
    def edit(m):
        at = m
        for key in path[:-1]:
            at = at[key]
        del at[path[-1]]
    return edit


def _more_four_chip_cells(m):
    for w in m["workloads"]:
        w["chips"] = 4


def _twice(kind):
    def edit(m):
        m[kind].append(copy.deepcopy(m[kind][0]))
    return edit


FAULTS = {
    "name_with_space": (_set(["workloads", 0, "name"], "bert base"), "permitted characters"),
    "name_with_slash": (_set(["end_to_end", 0, "name"], "samples/s"), "permitted characters"),
    "name_too_long": (_set(["per_layer", 0, "name"], "x" * 65), "permitted characters"),
    "unit_with_space": (_set(["end_to_end", 0, "unit"], "samples per s"), "unit"),
    "unit_too_long": (_set(["end_to_end", 0, "unit"], "samples/s/chip/run"), "unit"),
    "unit_greek": (_set(["per_layer", 0, "unit"], "\u03bcs"), "unit"),
    "better_sideways": (_set(["per_layer", 0, "better"], "sideways"), "better"),
    "source_unknown": (_set(["per_layer", 0, "source"], "guess"), "source"),
    "end_to_end_from_counter": (_set(["end_to_end", 0, "source"], "program_counter"), "takes only"),
    "bound_too_wide": (_set(["end_to_end", 0, "bound"], 0.2), "bound"),
    "bound_under_one_percent": (_set(["end_to_end", 0, "bound"], 0.001), "bound"),
    "bound_missing": (_del(["end_to_end", 0, "bound"]), "missing"),
    "why_on_a_metric": (_set(["end_to_end", 0, "why"], "because"), "extra"),
    "no_setup_s": (_set(["end_to_end", 4, "name"], "set_up"), "setup_s"),
    "moves_nothing": (_set(["per_layer", 0, "moves"], "happiness"), "no end_to_end metric"),
    "no_reader_file": (_set(["per_layer", 0, "name"], "unread_metric"), "no layer_metrics/"),
    "no_traffic_file": (_set(["workloads", 0, "traffic"], "absent"), "no traffic/"),
    "no_such_config": (_set(["workloads", 0, "config"], "absent"), "no config"),
    "config_file_outside_paths": (_set(["configs", 0, "file"], "configs/bert_base.json"), "not under paths"),
    "config_file_missing": (_set(["configs", 0, "file"], "benchmark/configs/absent.json"), "cannot be read"),
    "config_source_differs": (_set(["configs", 0, "source"], "https://example.org/other"), "source differs"),
    "reduced_differs": (_set(["configs", 0, "reduced"], ["num_hidden_layers"]), "reduced differs"),
    "chips_two": (_set(["workloads", 0, "chips"], 2), "chips is not 1 or 4"),
    "too_many_four_chip_cells": (_more_four_chip_cells, "ask for four chips"),
    "cell_twice": (_twice("workloads"), "appears twice"),
    "metric_twice": (_twice("per_layer"), "appears twice"),
    "one_cell_only": (lambda m: m["workloads"].__delitem__(slice(1, None)), "2 to 24 cells"),
    "why_on_two_lines": (_set(["workloads", 0, "why"], "one\ntwo"), "one line"),
    "why_too_long": (_set(["workloads", 0, "why"], "w" * 201), "one line"),
    "run_seconds_too_long": (_set(["run_seconds"], 52), "run_seconds"),
    "run_seconds_fraction": (_set(["run_seconds"], 20.5), "run_seconds"),
    "command_outside_paths": (_set(["command"], ["python3", "bench.py"]), "outside paths"),
    "command_absolute": (_set(["command"], ["python3", "/root/repo/benchmark/run.py"]), "leads out"),
    "path_leads_out": (_set(["paths"], ["benchmark", "../elsewhere"]), "permitted characters"),
    "path_missing": (_set(["paths"], ["benchmark", "tests/benchmark", "absent_dir"]), "no directory"),
    "extra_top_level_key": (_set(["notes"], "hello"), "not exactly"),
    "metric_lists_unknown_cell": (_set(["per_layer", 0, "workloads"], ["absent.cell"]), "no such workload"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_check_refuses(sound, fault):
    edit, says = FAULTS[fault]
    m = copy.deepcopy(sound)
    edit(m)
    faults = mf.check(m)
    assert any(says in f for f in faults), (fault, faults)


def test_one_four_chip_cell_is_always_allowed(sound):
    assert sum(w["chips"] == 4 for w in sound["workloads"]) == 1
    assert len(sound["workloads"]) < 8  # a quarter, rounded down, is 0


def _line(traced):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 15e9}
    line = {"correct": True, "attempted": 250, "failed": 0, "device": device}
    if traced:
        device.update(busy_s=1.5, window_s=1.6)
        line["metrics"] = {"device_step_ms": {"value": 78.0, "unit": "ms"}}
        line["breakdown"] = {"device_ops": [], "idle_gaps": []}
    else:
        line["metrics"] = {
            "samples_per_s_per_chip": {"value": 200.0, "unit": "samples/s/chip"},
            "step_ms_p50": {"value": 80.0, "unit": "ms"},
            "step_ms_p95": {"value": 81.0, "unit": "ms"},
            "mfu_pct": {"value": 35.0, "unit": "%"},
            "setup_s": {"value": 16.0, "unit": "s"},
        }
    return line


@pytest.mark.parametrize("traced", [False, True])
def test_a_sound_result_line_passes(sound, traced):
    assert mf.check_result_line(
        _line(traced), sound, "bert_base.ssgd_1chip", traced) == []


LINE_FAULTS = {
    "extra_key": lambda l: l.update(versions={"jax": "0.9.0"}),
    "ok_for_correct": lambda l: l.update(ok=l.pop("correct")),
    "breakdown_untraced": lambda l: l.update(breakdown={}),
    "metric_of_the_other_kind": lambda l: l["metrics"].update(
        device_step_ms={"value": 1.0, "unit": "ms"}),
    "wrong_unit": lambda l: l["metrics"].update(
        step_ms_p50={"value": 0.08, "unit": "s"}),
    "missing_metric": lambda l: l["metrics"].pop("setup_s"),
    "device_without_memory": lambda l: l["device"].pop("memory_peak_bytes"),
    "extra_key_in_metric": lambda l: l["metrics"]["mfu_pct"].update(p95=1),
}


@pytest.mark.parametrize("fault", sorted(LINE_FAULTS))
def test_result_line_check_refuses(sound, fault):
    line = _line(False)
    LINE_FAULTS[fault](line)
    assert mf.check_result_line(line, sound, "bert_base.ssgd_1chip", False)


def test_traced_line_needs_busy_and_window(sound):
    line = _line(True)
    del line["device"]["busy_s"]
    assert mf.check_result_line(line, sound, "bert_base.ssgd_1chip", True)


def test_run_check_passes_without_jax():
    """`run.py --check` exits 0 on the tree's manifest, and the parent
    process of the command never imports jax."""
    run = os.path.join(mf.BENCH_DIR, "run.py")
    probe = (
        "import runpy, sys\n"
        f"sys.argv = [{run!r}, '--check']\n"
        "try:\n"
        f"    runpy.run_path({run!r}, run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    code = e.code\n"
        "assert 'jax' not in sys.modules, 'run.py imported jax'\n"
        "sys.exit(code)\n"
    )
    done = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                          text=True, timeout=60)
    assert done.returncode == 0, done.stdout + done.stderr
    assert "sound" in done.stdout


def test_run_refuses_an_unknown_workload():
    run = os.path.join(mf.BENCH_DIR, "run.py")
    done = subprocess.run(
        [sys.executable, run, "--workload", "absent.cell", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=60)
    assert done.returncode != 0
    assert not done.stdout.strip().startswith("{")


def test_the_parent_builds_every_cells_command_without_jax():
    """The launcher's side of the parent (`launchers/<name>.argv`) imports
    no jax either, and the kfrun cell's command is a `kfrun` tree of
    children."""
    run = os.path.join(mf.BENCH_DIR, "run.py")
    probe = (
        "import argparse, importlib.util, json, sys\n"
        f"spec = importlib.util.spec_from_file_location('bench_run', {run!r})\n"
        "run = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(run)\n"
        "m = run.manifest.load()\n"
        "args = argparse.Namespace(seed=1, seconds=1.0, trace=0)\n"
        "out = {w['name']: run.child_argv(run.manifest.cell(m, w['name']), args, 'out')\n"
        "       for w in m['workloads']}\n"
        "assert 'jax' not in sys.modules, 'the parent imported jax'\n"
        "print(json.dumps(out))\n"
    )
    done = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                          text=True, timeout=60)
    assert done.returncode == 0, done.stdout + done.stderr
    argv = json.loads(done.stdout.splitlines()[-1])
    child = os.path.join(mf.BENCH_DIR, "child.py")
    assert argv["bert_base.ssgd_1chip"][1] == child
    kfrun = argv["bert_base.ssgd_kfrun_4chip"]
    assert kfrun[1:3] == ["-m", "kungfu_tpu.runner.cli"]
    assert kfrun[3:9] == ["-np", "4", "-H", "127.0.0.1:4", "-devices-per-host", "4"]
    assert kfrun[9] == "--" and kfrun[11] == child
    assert "--workload" in kfrun and "bert_base.ssgd_kfrun_4chip" in kfrun


@pytest.mark.parametrize("kind", sorted(mf.TRAFFIC_PLUGINS))
def test_a_traffic_file_that_names_a_missing_file_is_refused(tmp_path, kind):
    """A traffic file names its launcher, step factory, optimizer and
    placement; each is a file of the benchmark's, and `--check` says so
    when it is not there."""
    body = mf._read_json("traffic", "ssgd_1chip.json")
    for k in mf.TRAFFIC_PLUGINS:
        (tmp_path / k).mkdir()
        (tmp_path / k / (mf.TRAFFIC_PLUGINS[k](body) + ".py")).write_text("")
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "mix.json").write_text(json.dumps(body))
    assert mf._traffic_faults(str(tmp_path), "mix") == []
    (tmp_path / kind / (mf.TRAFFIC_PLUGINS[kind](body) + ".py")).unlink()
    (fault,) = mf._traffic_faults(str(tmp_path), "mix")
    assert f"no {kind}/" in fault
    assert mf._traffic_faults(str(tmp_path), "absent") == ["no traffic/absent.json"]


def test_a_traffic_file_without_a_step_is_refused(tmp_path):
    body = mf._read_json("traffic", "ssgd_1chip.json")
    del body["step"]
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "mix.json").write_text(json.dumps(body))
    assert "traffic/mix.json names none of steps/" in mf._traffic_faults(
        str(tmp_path), "mix")


def test_plugin_names_are_names():
    with pytest.raises(ValueError, match="permitted characters"):
        mf.plugin("launchers", "../run")


@pytest.mark.parametrize("cell", [w["name"] for w in mf.load()["workloads"]])
def test_every_cell_finds_its_files(sound, cell):
    c = mf.cell(sound, cell)
    assert c["config"]["family"] in ("transformer", "resnet")
    assert c["traffic"]["launcher"] in ("none", "kfrun")
    assert c["traffic"]["mesh"] == {"dp": c["chips"]}
    json.dumps(c)  # plain data all the way down
    # and everything the traffic file names is there, with its entry points
    found = {kind: mf.plugin(kind, named(c["traffic"]))
             for kind, named in mf.TRAFFIC_PLUGINS.items()}
    assert callable(found["launchers"].argv) and callable(found["launchers"].join)
    assert callable(found["steps"].build) and callable(found["steps"].place)
    assert found["steps"].BATCH_AXIS == "dp"
    assert callable(found["optimizers"].make) and callable(found["placements"].make)
    family = mf.plugin("families", c["config"]["family"])
    for name in ("init", "trainable", "host_batch", "flops_per_sample",
                 "head_width", "program_loss_and_grads",
                 "reference_loss_and_grads"):
        assert callable(getattr(family, name)), name
    assert hasattr(family, "loss_fn") != hasattr(family, "local_step")
    assert 0 < family.LOSS_RTOL < family.GRAD_RTOL < 0.1
