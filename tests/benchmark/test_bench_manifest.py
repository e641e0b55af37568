"""BENCHMARK.json's self-check: the manifest in the tree is sound, and each
rule of the contract that can be checked without a chip refuses its fault —
first of all the one PR 22 was refused for."""

import copy
import importlib.util
import json
import math
import os
import shutil
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
    "command_outside_paths": (_set(["command"], ["python3", "chip_smoke.py"]), "outside paths"),
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


THIRD_FAMILY = '''"""A third family, as a `model_config` PR would add one: its own keys in
its configuration file, and the entry points the harness calls."""

REFERENCE_SAMPLES = 1
LOSS_RTOL = 1e-4
GRAD_RTOL = 1e-2


def init(cfg, seed): ...
def loss_fn(cfg): ...
def trainable(state): ...
def head_width(cfg): ...
def program_loss_and_grads(cfg): ...
def reference_loss_and_grads(cfg, state, batch): ...
def host_batch(cfg, seed, i, n): ...
def flops_per_sample(cfg): ...
'''


@pytest.fixture(scope="module")
def scratch(tmp_path_factory):
    """A copy of the benchmark in a scratch repo, with what the next PRs
    bring as files alone: a third family with a configuration of other keys
    than the transformer's seven, a traffic mix over a mesh of two axes
    under a third launcher, a per-layer metric of the new cells' own, and
    24 traffic files more, so that a manifest of any size up to the
    contract's 24 cells can be built on it. -> (repo, bench_dir)."""
    repo = tmp_path_factory.mktemp("scratch_repo")
    bench = repo / "benchmark"
    shutil.copytree(mf.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "testdata"))
    (repo / "tests" / "benchmark").mkdir(parents=True)
    (bench / "families" / "sparse_experts.py").write_text(THIRD_FAMILY)
    (bench / "configs" / "third.json").write_text(json.dumps({
        "family": "sparse_experts", "source": "https://example.org/third/config.json",
        "hidden_size": 2048, "num_experts": 64, "num_experts_per_tok": 8,
        "rope_theta": 10000.0, "param_dtype": "float32",
        "compute_dtype": "bfloat16", "head_dtype": "float32", "reduced": []}))
    body = mf._read_json("traffic", "ssgd_1chip.json")
    shutil.copy(bench / "launchers" / "none.py", bench / "launchers" / "one_host.py")
    (bench / "traffic" / "dp2tp2_4chip.json").write_text(json.dumps(
        dict(body, mesh={"dp": 2, "tp": 2}, launcher="one_host")))
    shutil.copy(bench / "layer_metrics" / "fwd_ms.py",
                bench / "layer_metrics" / "fwd_ms.third.py")
    for i in range(24):
        (bench / "traffic" / f"mix_{i:02d}.json").write_text(json.dumps(body))
    return str(repo), str(bench)


def _manifest_of(sound, cells, four):
    """The tree's manifest with `cells` cells, the first `four` of them on
    four chips, over the scratch tree's 24 traffic files and the first
    `cells` of the tree's configurations, however many it has; every
    configuration kept is used."""
    m = copy.deepcopy(sound)
    m["configs"] = m["configs"][:cells]
    configs = [c["name"] for c in m["configs"]]
    m["workloads"] = [
        {"name": f"{configs[i % len(configs)]}.mix_{i:02d}",
         "config": configs[i % len(configs)], "traffic": f"mix_{i:02d}",
         "chips": 4 if i < four else 1, "why": "a cell of a manifest built in a test"}
        for i in range(cells)]
    for metric in m["end_to_end"] + m["per_layer"]:
        metric.pop("workloads", None)
    return m


@pytest.mark.parametrize("cells,four,sound_", [
    (3, 1, True), (3, 2, False), (4, 1, True), (7, 1, True), (7, 2, False),
    (8, 2, True), (8, 3, False), (24, 6, True), (24, 7, False)])
def test_one_four_chip_cell_is_always_allowed(sound, scratch, cells, four, sound_):
    """A quarter of the cells, rounded down, may ask for four chips, and
    one always may: asked of `manifest.check` about manifests built here,
    whatever the tree's own manifest holds today."""
    repo, bench = scratch
    faults = mf.check(_manifest_of(sound, cells, four), bench, repo)
    if sound_:
        assert faults == []
    else:
        allowed = max(1, math.floor(cells / 4))
        assert faults == [f"{four} of {cells} cells ask for four chips; at "
                          f"most {allowed} may"]


def _line(traced):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 15e9, "backend_start_s": 8.0,
              "command_to_window_s": 24.0}
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
    line["compared"] = {"grad_error": [0.0125, 0.04], "steps_failed": [0, 0]}
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
    "device_without_backend_start": lambda l: l["device"].pop("backend_start_s"),
    "device_without_the_whole_setup": lambda l: l["device"].pop("command_to_window_s"),
    "extra_key_in_metric": lambda l: l["metrics"]["mfu_pct"].update(p95=1),
    "nothing_compared": lambda l: l.pop("compared"),
    "compared_not_last": lambda l: l.update(device=l.pop("device")),
    "compared_without_its_limit": lambda l: l["compared"].update(loss_error=1e-5),
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


def _holds_what_the_harness_needs(c: dict, find) -> None:
    """What `child.py` and `harness.measure` need of a cell's files, and
    nothing about which families, launchers or meshes exist today.
    `find(kind, name)` is the module `<kind>/<name>.py`."""
    json.dumps(c)  # plain data all the way down
    mesh = c["traffic"]["mesh"]  # -> parallel.make_mesh: {axis: size}
    assert mesh and all(isinstance(k, str) and isinstance(v, int) and v >= 1
                        for k, v in mesh.items()), mesh
    assert math.prod(mesh.values()) == c["chips"]
    assert c["traffic"]["per_chip_batch"] >= 1 and c["traffic"]["pool"] >= 1
    # everything the traffic file names is there, with its entry points
    found = {kind: find(kind, named(c["traffic"]))
             for kind, named in mf.TRAFFIC_PLUGINS.items()}
    assert callable(found["launchers"].argv) and callable(found["launchers"].join)
    assert callable(found["steps"].build) and callable(found["steps"].place)
    assert found["steps"].BATCH_AXIS in mesh
    assert callable(found["optimizers"].make) and callable(found["placements"].make)
    family = find("families", c["config"]["family"])
    for name in ("init", "trainable", "host_batch", "flops_per_sample",
                 "head_width", "program_loss_and_grads",
                 "reference_loss_and_grads"):
        assert callable(getattr(family, name)), name
    assert hasattr(family, "loss_fn") != hasattr(family, "local_step")
    assert family.REFERENCE_SAMPLES >= 1
    assert 0 < family.LOSS_RTOL < family.GRAD_RTOL < 0.1
    # what `precision_faults` holds the program to: types jax knows
    import jax.numpy as jnp

    for key in ("param_dtype", "head_dtype"):
        assert jnp.issubdtype(jnp.dtype(c["config"][key]), jnp.floating), key


@pytest.mark.parametrize("cell", [w["name"] for w in mf.load()["workloads"]])
def test_every_cell_finds_its_files(sound, cell):
    _holds_what_the_harness_needs(mf.cell(sound, cell), mf.plugin)


def _module_at(bench: str):
    def find(kind, name):
        spec = importlib.util.spec_from_file_location(
            f"scratch_{kind}_{name}", os.path.join(bench, kind, name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
    return find


NEW_CELLS = 5


def _eight_cells(sound):
    """The tree's cells and what R1 and R7 would add as files alone: a
    new family on one chip and on a dp x tp mesh of four, NEW_CELLS cells
    more (eight or more in all), two of them on four chips; and a
    per-layer metric for the new cells only."""
    m = copy.deepcopy(sound)
    m["configs"].append({
        "name": "third", "source": "https://example.org/third/config.json",
        "file": "benchmark/configs/third.json", "reduced": [],
        "why": "a third family: routed experts, rotary positions"})
    for config, traffic, chips in [
            ("third", "ssgd_1chip", 1), ("third", "dp2tp2_4chip", 4),
            ("third", "ssgd_1chip_b128", 1), ("bert_base", "ssgd_1chip_b128", 1),
            ("resnet50", "mix_00", 1)]:
        m["workloads"].append({
            "name": f"{config}.{traffic}", "config": config, "traffic": traffic,
            "chips": chips, "why": "a cell that a later PR adds as files alone"})
    m["per_layer"].append(dict(m["per_layer"][-1], name="fwd_ms.third",
                               workloads=["third.ssgd_1chip", "third.dp2tp2_4chip"]))
    return m


def test_the_next_family_mesh_and_eighth_cell_come_as_files(sound, scratch, monkeypatch):
    """A scratch copy of the benchmark takes a third family file, a traffic
    file over {"dp": 2, "tp": 2}, a per-layer metric reported in the new
    cells alone and an eighth cell, the second on four chips, with no edit
    to a file that is there: `manifest.check` finds it sound, and every
    cell holds to what the harness needs. A closed list of families,
    launchers, meshes or cell counts in either fails here, not in the
    `model_config` PR that adds the files."""
    repo, bench = scratch
    m = _eight_cells(sound)
    assert mf.check(m, bench, repo) == []
    assert len(m["workloads"]) == len(sound["workloads"]) + NEW_CELLS >= 8
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 2
    monkeypatch.setattr(mf, "REPO", repo)
    monkeypatch.setattr(mf, "BENCH_DIR", bench)
    for w in m["workloads"]:
        _holds_what_the_harness_needs(mf.cell(m, w["name"]), _module_at(bench))
    new = mf.cell(m, "third.dp2tp2_4chip")
    assert new["config"]["family"] == "sparse_experts"
    assert new["traffic"]["mesh"] == {"dp": 2, "tp": 2}
    # the new metric is in the new cells' traced lines and in no other
    assert "fwd_ms.third" in {x["name"] for x in mf.metrics_of(
        m, "per_layer", "third.dp2tp2_4chip")}
    assert "fwd_ms.third" not in {x["name"] for x in mf.metrics_of(
        m, "per_layer", "bert_base.ssgd_1chip")}


@pytest.mark.parametrize("closed_list,refuses", [
    ("family", ["third.ssgd_1chip", "third.dp2tp2_4chip", "third.ssgd_1chip_b128"]),
    ("launcher", ["third.dp2tp2_4chip"]),
    ("mesh", ["third.dp2tp2_4chip"]),
])
def test_the_parents_closed_lists_refuse_the_scratch_tree(sound, scratch, monkeypatch,
                                                           closed_list, refuses):
    """The three whitelists this file held every cell to until PR 26
    (family among the tree's own, launcher in (none, kfrun), mesh ==
    {dp: chips}): put back, each refuses the scratch tree's new cells,
    which `_holds_what_the_harness_needs` takes."""
    repo, bench = scratch
    monkeypatch.setattr(mf, "REPO", repo)
    monkeypatch.setattr(mf, "BENCH_DIR", bench)
    m = _eight_cells(sound)
    families = {mf.cell(sound, w["name"])["config"]["family"]
                for w in sound["workloads"]}
    parents = {
        "family": lambda c: c["config"]["family"] in families,
        "launcher": lambda c: c["traffic"]["launcher"] in ("none", "kfrun"),
        "mesh": lambda c: c["traffic"]["mesh"] == {"dp": c["chips"]},
    }
    assert [w["name"] for w in m["workloads"]
            if not parents[closed_list](mf.cell(m, w["name"]))] == refuses
