"""BENCHMARK.json and the files it names: loading, and the self-check.

No jax here: the parent of a run and `run.py --check` import this module.
Everything that belongs to one cell is found by name, the first three by the
names in the manifest and the rest by those in the traffic file:

    benchmark/configs/<config>.json         sizes, source, reduced, assumed
    benchmark/families/<family>.py          the config's "family": model,
                                            batches, operation count,
                                            reference and its tolerances
    benchmark/layer_metrics/<metric>.py     one reader per per-layer metric
    benchmark/traffic/<traffic>.json        mesh, per-chip batch, pool, and:
    benchmark/launchers/<launcher>.py         how the processes start and join
    benchmark/steps/<step>.py                 the train-step factory
    benchmark/optimizers/<optimizer.name>.py  the base optimizer
    benchmark/placements/<placement>.py       host batch -> device batch

so a later PR adds files and entries and edits nothing that is here.
"""

from __future__ import annotations

import importlib
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(REPO, "BENCHMARK.json")

# the contract's limits (builder's instructions, PR 23)
MANIFEST_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}
# `compared`, each number `correct` was decided on beside its limit, comes last
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device", "compared")
TRACED_RESULT_KEYS = RESULT_KEYS[:-1] + ("breakdown", "compared")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
END_TO_END_SOURCES = {"host_clock", "device_trace"}
MAX_BOUND = 0.1
FOUR_CHIP_SHARE = 0.25


# the traffic file's keys that name a file, by the directory it is in
TRAFFIC_PLUGINS = {
    "launchers": lambda t: t["launcher"],
    "steps": lambda t: t["step"],
    "optimizers": lambda t: t["optimizer"]["name"],
    "placements": lambda t: t["placement"],
}


def plugin(kind: str, name: str):
    """The module `benchmark/<kind>/<name>.py`."""
    if not NAME.match(name):
        raise ValueError(f"{kind} name {name!r} is not of the permitted characters")
    return importlib.import_module(f"benchmark.{kind}.{name}")


def load(path: str = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def _read_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def cell(manifest: dict, workload: str) -> dict:
    """One entry of `workloads` with its configuration and traffic files
    read in: {"name", "chips", "config_name", "config", "traffic", ...}."""
    for w in manifest["workloads"]:
        if w["name"] == workload:
            break
    else:
        known = [w["name"] for w in manifest["workloads"]]
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json: {known}")
    (cfg_entry,) = [c for c in manifest["configs"] if c["name"] == w["config"]]
    with open(os.path.join(REPO, cfg_entry["file"])) as f:
        config = json.load(f)
    return {
        "name": w["name"],
        "chips": w["chips"],
        "config_name": w["config"],
        "config": config,
        "traffic": _read_json("traffic", w["traffic"] + ".json"),
    }


def reported_in(metric: dict, manifest: dict) -> list:
    """The cells that report a metric: those it lists, or all."""
    return metric.get("workloads") or [w["name"] for w in manifest["workloads"]]


def metrics_of(manifest: dict, kind: str, workload: str) -> list:
    """The `end_to_end` or `per_layer` entries a cell reports."""
    return [m for m in manifest[kind] if workload in reported_in(m, manifest)]


def _one_line(problems, what, text, limit=200):
    if not (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text):
        problems.append(f"{what}: not one line of 1 to {limit} characters")


def _traffic_faults(bench_dir: str, traffic: str) -> list:
    """A traffic file that is missing or names a file that is."""
    try:
        with open(os.path.join(bench_dir, "traffic", traffic + ".json")) as f:
            body = json.load(f)
    except (OSError, ValueError):
        return [f"no traffic/{traffic}.json"]
    faults = []
    for kind, named in TRAFFIC_PLUGINS.items():
        try:
            name = named(body)
        except (KeyError, TypeError):
            faults.append(f"traffic/{traffic}.json names none of {kind}/")
            continue
        if not (isinstance(name, str) and NAME.match(name) and os.path.isfile(
                os.path.join(bench_dir, kind, name + ".py"))):
            faults.append(f"traffic/{traffic}.json: no {kind}/{name}.py")
    return faults


def check(manifest: dict, bench_dir: str = BENCH_DIR, repo: str = REPO) -> list:
    """Every fault found in the manifest and the files it names, as
    sentences; empty when it is sound. The rules are the contract's, as far
    as they can be checked without a chip."""
    p = []
    if set(manifest) != MANIFEST_KEYS:
        p.append(f"keys {sorted(manifest)} are not exactly {sorted(MANIFEST_KEYS)}")
        return p
    if not (isinstance(manifest["run_seconds"], int)
            and 1 <= manifest["run_seconds"] <= 51):
        p.append("run_seconds is not a whole number from 1 to 51")
    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16:
        p.append("paths has not 1 to 16 directories")
    for d in paths:
        if not PATH.match(d) or d.startswith("/") or ".." in d.split("/"):
            p.append(f"path {d!r} is not a relative path of the permitted characters")
        elif not os.path.isdir(os.path.join(repo, d)):
            p.append(f"path {d!r} is no directory")
    if not 1 <= len(manifest["command"]) <= 32:
        p.append("command has not 1 to 32 words")
    for word in manifest["command"]:
        _one_line(p, f"command word {word!r}", word)
        if word.startswith("/") or ".." in word.split("/"):
            p.append(f"command word {word!r} leads out of the repo")
        elif os.path.exists(os.path.join(repo, word)) and not any(
                word == d or word.startswith(d + "/") for d in paths):
            p.append(f"command names {word!r}, a file outside paths")

    def names(kind, entries, keys, optional=()):
        seen = set()
        for e in entries:
            extra = set(e) - set(keys) - set(optional)
            missing = set(keys) - set(e)
            if extra or missing:
                p.append(f"{kind} {e.get('name')!r}: keys {sorted(e)} "
                         f"(extra {sorted(extra)}, missing {sorted(missing)})")
            n = e.get("name", "")
            if not NAME.match(n):
                p.append(f"{kind} name {n!r} is not of the permitted characters")
            if n in seen:
                p.append(f"{kind} name {n!r} appears twice")
            seen.add(n)
        return seen

    configs = manifest["configs"]
    config_names = names("config", configs,
                         ("name", "source", "file", "reduced", "why"))
    if not 1 <= len(configs) <= 24:
        p.append("configs has not 1 to 24 entries")
    files = set()
    for c in configs:
        _one_line(p, f"config {c['name']} source", c.get("source"))
        _one_line(p, f"config {c['name']} why", c.get("why"))
        f = c.get("file", "")
        if f in files:
            p.append(f"config file {f!r} is used twice")
        files.add(f)
        if not any(f.startswith(d + "/") for d in paths):
            p.append(f"config file {f!r} is not under paths")
        if len(c.get("reduced", [])) > 16:
            p.append(f"config {c['name']}: more than 16 reduced keys")
        for key in c.get("reduced", []):
            if not NAME.match(key):
                p.append(f"config {c['name']}: reduced key {key!r}")
        try:
            with open(os.path.join(repo, f)) as fh:
                body = json.load(fh)
        except (OSError, ValueError) as e:
            p.append(f"config file {f!r} cannot be read: {e}")
            continue
        family = body.get("family", "")
        if not os.path.isfile(os.path.join(bench_dir, "families", family + ".py")):
            p.append(f"config {c['name']}: no families/{family}.py")
        if body.get("source") != c.get("source"):
            p.append(f"config {c['name']}: the file's source differs from the manifest's")
        if sorted(body.get("reduced", [])) != sorted(c.get("reduced", [])):
            p.append(f"config {c['name']}: the file's reduced differs from the manifest's")

    cells = manifest["workloads"]
    cell_names = names("workload", cells,
                       ("name", "config", "traffic", "chips", "why"))
    if not 2 <= len(cells) <= 24:
        p.append("workloads has not 2 to 24 cells")
    pairs = set()
    for w in cells:
        _one_line(p, f"workload {w['name']} why", w.get("why"))
        if w.get("config") not in config_names:
            p.append(f"workload {w['name']}: no config {w.get('config')!r}")
        if not NAME.match(w.get("traffic", "")):
            p.append(f"workload {w['name']}: traffic name {w.get('traffic')!r}")
        else:
            p.extend(f"workload {w['name']}: {fault}"
                     for fault in _traffic_faults(bench_dir, w["traffic"]))
        if w.get("chips") not in (1, 4):
            p.append(f"workload {w['name']}: chips is not 1 or 4")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            p.append(f"config and traffic {pair} appear twice")
        pairs.add(pair)
    for c in config_names - {w.get("config") for w in cells}:
        p.append(f"config {c!r} is used by no cell")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, int(len(cells) * FOUR_CHIP_SHARE)):
        p.append(f"{four} of {len(cells)} cells ask for four chips; at most "
                 f"{max(1, int(len(cells) * FOUR_CHIP_SHARE))} may")

    metric_keys = ("name", "unit", "better", "source")
    e2e = manifest["end_to_end"]
    layer = manifest["per_layer"]
    e2e_names = names("end_to_end metric", e2e, metric_keys + ("bound",),
                      ("workloads",))
    layer_names = names("per_layer metric", layer,
                        metric_keys + ("layer", "moves"), ("workloads",))
    if not 1 <= len(e2e) <= 16:
        p.append("end_to_end has not 1 to 16 metrics")
    if not 1 <= len(layer) <= 128:
        p.append("per_layer has not 1 to 128 metrics")
    for n in e2e_names & layer_names:
        p.append(f"metric name {n!r} is both end-to-end and per-layer")
    for m in e2e + layer:
        if not UNIT.match(m.get("unit", "")):
            p.append(f"metric {m['name']}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            p.append(f"metric {m['name']}: better is not lower or higher")
        if m.get("source") not in SOURCES:
            p.append(f"metric {m['name']}: source {m.get('source')!r}")
        for w in m.get("workloads", []):
            if w not in cell_names:
                p.append(f"metric {m['name']} lists no such workload {w!r}")
    for m in e2e:
        if m.get("source") not in END_TO_END_SOURCES:
            p.append(f"end_to_end metric {m['name']} takes only host_clock "
                     "or device_trace")
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and 0.01 <= b <= MAX_BOUND):
            p.append(f"end_to_end metric {m['name']}: bound {b!r} is not "
                     f"from 0.01 to {MAX_BOUND}")
    if "setup_s" not in e2e_names:
        p.append("no end_to_end metric setup_s")
    for m in layer:
        _one_line(p, f"metric {m['name']} layer", m.get("layer"))
        if not os.path.isfile(
                os.path.join(bench_dir, "layer_metrics", m["name"] + ".py")):
            p.append(f"per_layer metric {m['name']}: no layer_metrics/{m['name']}.py")
        if m.get("moves") not in e2e_names:
            p.append(f"per_layer metric {m['name']} moves {m.get('moves')!r}, "
                     "which is no end_to_end metric")
            continue
        (moved,) = [e for e in e2e if e["name"] == m["moves"]]
        for w in reported_in(m, manifest):
            # the fault PR 22 was refused for
            if w not in reported_in(moved, manifest):
                p.append(f"per_layer metric {m['name']} is reported on workload "
                         f"{w}, where {moved['name']}, which it should move, is not")
    for w in cell_names:
        mine = [m["name"] for m in metrics_of(manifest, "end_to_end", w)]
        if "setup_s" not in mine or len(mine) < 2:
            p.append(f"workload {w} does not report setup_s and one other "
                     "end_to_end metric")
        if not metrics_of(manifest, "per_layer", w):
            p.append(f"workload {w} reports no per_layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        p.append("the manifest is over 64 KiB")
    return p


def check_result_line(line: dict, manifest: dict, workload: str,
                      traced: bool) -> list:
    """Faults in a run's last line: its keys are exactly the contract's,
    its metrics are those the cell reports in this kind of run, and what
    was compared comes last, each number beside its limit."""
    p = []
    allowed = TRACED_RESULT_KEYS if traced else RESULT_KEYS
    if not set(RESULT_KEYS) <= set(line) <= set(allowed):
        p.append(f"result keys {sorted(line)} are not {list(allowed)}")
        return p
    kind = "per_layer" if traced else "end_to_end"
    units = {m["name"]: m["unit"] for m in metrics_of(manifest, kind, workload)}
    for name, body in line["metrics"].items():
        if name not in units:
            p.append(f"metric {name} is not a {kind} metric of {workload}")
        elif set(body) != {"value", "unit"} or body["unit"] != units[name]:
            p.append(f"metric {name}: {body} is not a value in {units[name]}")
    if not traced:
        for name in set(units) - set(line["metrics"]):
            p.append(f"end_to_end metric {name} is missing")
    want = {"platform", "kind", "count", "memory_peak_bytes",
            "backend_start_s", "command_to_window_s"}
    if traced:
        want |= {"busy_s", "window_s"}
    if set(line["device"]) != want:
        p.append(f"device keys {sorted(line['device'])} are not {sorted(want)}")
    if list(line)[-1] != "compared":
        p.append("compared is not the last key")
    for name, pair in line["compared"].items():
        if not (isinstance(pair, list) and len(pair) == 2 and all(
                isinstance(x, (int, float)) for x in pair)):
            p.append(f"compared {name}: {pair} is not [number, limit]")
    return p
