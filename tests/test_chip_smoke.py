"""chip_smoke.py off the chip: its phase bodies at tiny() size on the CPU
mesh, and the guarantees of its entry point — it fails without a TPU,
its parent never imports jax, the compile cache stays where it is put."""

import json
import os
import subprocess
import sys

import pytest

from ports import kfrun_ports

import chip_smoke
from kungfu_tpu.models.transformer import TransformerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def train_result():
    return chip_smoke.train_phase(TransformerConfig.tiny(), steps=3,
                                  per_chip_batch=2)


def test_train_phase_tiny(train_result):
    r = train_result
    assert r["mesh"] == {"dp": 8} and r["global_batch"] == 16
    assert r["losses"][-1] < r["losses"][0]
    assert r["compiles_first_step"] >= 1
    assert r["compiles_after_first_step"] == 0
    assert len(r["step_s"]) == 3
    # the CPU backend keeps no memory statistics; the chip entry
    # requires them
    with pytest.raises(chip_smoke.SmokeFailure, match="bytes in use"):
        chip_smoke._require_memory_in_use(r, 1)


def test_train_sharded_phase_tiny_agrees_with_train(train_result):
    r = chip_smoke.train_sharded_phase(
        TransformerConfig.tiny(), steps=3,
        global_batch=train_result["global_batch"],
        reference_losses=train_result["losses"][:3],
    )
    assert r["mesh"] == {"dp": 2, "tp": 4}
    assert max(r["loss_drift"]) <= chip_smoke.LOSS_RTOL
    assert r["compiles_after_first_step"] == 0


def test_train_sharded_phase_rejects_other_losses(train_result):
    with pytest.raises(chip_smoke.SmokeFailure, match="differ from"):
        chip_smoke.train_sharded_phase(
            TransformerConfig.tiny(), steps=1,
            global_batch=train_result["global_batch"],
            reference_losses=[2 * l for l in train_result["losses"][:3]],
        )


def test_kernels_phase_interpreted():
    r = chip_smoke.kernels_phase(seq=64, head_dims=(16, 32), interpret=True,
                                 blk=32)
    assert set(r["errors"]) == {
        f"hd{hd}_{t}" for hd in (16, 32) for t in ("out", "dq", "dk", "dv")
    }
    assert max(r["errors"].values()) <= chip_smoke.KERNEL_TOL


def _smoke_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


_WORKER = """
import json, sys
import chip_smoke
from kungfu_tpu.models.transformer import TransformerConfig
body = getattr(chip_smoke, sys.argv[1])
print(chip_smoke.RESULT_TAG + json.dumps(body(TransformerConfig.tiny(), 2, 2)),
      flush=True)
"""


@pytest.mark.parametrize("body,local_devices", [
    ("launcher_worker", 1),  # two one-device workers joined into one world
    ("hier_worker", 2),  # two two-device worlds bridged over the host plane
])
def test_worker_bodies_under_kfrun(body, local_devices):
    env = _smoke_env()
    env["PYTHONPATH"] = REPO
    env["JAX_NUM_CPU_DEVICES"] = str(local_devices)
    r = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.runner.cli", "-np", "2",
         *kfrun_ports().args,  # this xdist worker's block
         "-H", "127.0.0.1:2", "--", sys.executable, "-c", _WORKER, body],
        env=env, capture_output=True, text=True, timeout=240, cwd=REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    results = [json.loads(l.split(chip_smoke.RESULT_TAG, 1)[1])
               for l in r.stdout.splitlines() if chip_smoke.RESULT_TAG in l]
    assert sorted(w["rank"] for w in results) == [0, 1]
    for w in results:
        assert w["params_agree"] and w["compiles_after_first_step"] == 0
        assert len(w["local_devices"]) == local_devices
        assert w["device"]["count"] == 2


def test_entry_point_fails_on_cpu_naming_the_devices_phase():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=_smoke_env(), capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert "phase 'devices' FAILED" in r.stderr
    assert "no TPU" in r.stdout  # the child's reason, echoed
    # neither the report nor the result line
    assert chip_smoke.REPORT_TAG not in r.stdout
    assert not any(l.startswith("{") for l in r.stdout.splitlines())


def test_result_line_has_the_contract_keys_and_no_others():
    found = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
             "coords": [0, 0, 0]}
    line = chip_smoke.result_line(found)
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
    }


def _resize_results(sizes=(4, 2, 4)):
    """What the workers of a 4 -> 2 -> 4 job print, drawn: every rank of
    every incarnation, the later ones with a pause whose parts add up."""
    parts = dict.fromkeys(chip_smoke.PAUSE_PARTS, 1000.0)
    out = []
    for version, size in enumerate(sizes):
        for rank in range(size):
            r = {
                "rank": rank, "workers": size, "version": version,
                "slots": [rank], "local_devices": [rank], "coords": [[rank, 0, 0]],
                "process_bounds": "2,2,1", "first_step": 20 * version,
                "last_step": 20 * version + 20, "stop_reason": "reload",
                "first_loss": 9.0, "last_loss": 8.0, "slow_misses": [],
                "compile_requests": {"hit": 2, "miss": 0, "off": 0},
                "smoke_spans_ms": {}, "checkpoint_spans_ms": {},
                "resize_phases": {},
            }
            if version:
                r.update(
                    handed_digest="ab" * 32, restored_digest="ab" * 32,
                    ranks_agree=True, loss_before=8.0,
                    resize_phases={**parts, "unaccounted_ms": 500.0,
                                   "pause_ms": 10500.0, "compile_hits": 2,
                                   "compile_misses": 0},
                )
            out.append(r)
    return out


def test_resize_report_of_a_job_that_ran_as_asked():
    report = chip_smoke.resize_report(_resize_results(), [4, 2, 4])
    first, second, third = report["incarnations"]
    assert [i["workers"] for i in report["incarnations"]] == [4, 2, 4]
    assert "pause" not in first
    assert second["pause"]["pause_ms"] == 10500.0
    assert second["parts_by_rank"]["compile_ms"] == [1000.0, 1000.0]
    assert third["parts_by_rank"]["pause_ms"] == [10500.0] * 4
    assert second["slots"] == [[0], [1]]


def _lost_a_rank(rs):
    rs.pop()


def _restored_something_else(rs):
    rs[-1]["restored_digest"] = "cd" * 32


def _ranks_disagree(rs):
    rs[5]["ranks_agree"] = False


def _left_a_part_out(rs):
    rs[4]["resize_phases"]["kill_ms"] = None


def _parts_do_not_add_up(rs):
    rs[4]["resize_phases"]["pause_ms"] += 50.0


def _never_came_back(rs):
    del rs[6:]


@pytest.mark.parametrize("fault,says", [
    (_lost_a_rank, "incarnation 2: ranks"),
    (_restored_something_else, "incarnation 2, rank 3: restored"),
    (_ranks_disagree, "incarnation 1, rank 1: restored"),
    (_left_a_part_out, "no ['kill_ms']"),
    (_parts_do_not_add_up, "parts sum to"),
    (_never_came_back, "2 incarnations ran, not 3"),
])
def test_resize_report_refuses(fault, says):
    results = _resize_results()
    fault(results)
    with pytest.raises(chip_smoke.SmokeFailure) as e:
        chip_smoke.resize_report(results, [4, 2, 4])
    assert says in str(e.value)


@pytest.mark.parametrize("phase", ["train", "kernels", "hier-worker"])
def test_child_phases_refuse_the_cpu_before_compiling(phase):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), phase],
        env=_smoke_env(), capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert chip_smoke.RESULT_TAG not in r.stdout


def test_parent_imports_no_jax():
    code = ("import sys, chip_smoke; "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'kungfu_tpu')]; "
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   timeout=60)


def test_compile_cache_is_placed_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: left alone, nothing set in code.
    Unset: one absolute path inside the checkout, whatever the cwd."""
    code = (
        "import json, os, jax; "
        "from kungfu_tpu.parallel.chip import enable_compile_cache; "
        "print(json.dumps([enable_compile_cache(), "
        "jax.config.jax_compilation_cache_dir]))"
    )

    def run(cwd, placed=None):
        env = _smoke_env()
        env["PYTHONPATH"] = REPO
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if placed:
            env["JAX_COMPILATION_CACHE_DIR"] = placed
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env=env,
            capture_output=True, text=True, check=True, timeout=60,
        ).stdout
        return json.loads(out.strip().splitlines()[-1])

    placed = str(tmp_path / "placed")
    assert run(REPO, placed) == [placed, placed]
    a = run(REPO)
    b = run(str(tmp_path))
    assert a == b == [os.path.join(REPO, ".jax_cache")] * 2
    assert not os.path.exists(placed)  # nothing was compiled: not created
