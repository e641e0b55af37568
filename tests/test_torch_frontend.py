"""PyTorch frontend over the host plane (parity: kungfu/torch/__init__.py
+ module_cpu.cpp — the reference's second-framework contract)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from ports import kfrun_ports

torch = pytest.importorskip("torch")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "torch_agent.py")


def test_single_process_noops():
    """Cluster of one: sync/broadcast are no-ops, wrapper still steps."""
    from kungfu_tpu import torch as kf_torch

    model = torch.nn.Linear(2, 1, bias=False)
    with torch.no_grad():
        model.weight.fill_(1.0)
    kf_torch.broadcast_parameters(model)
    opt = kf_torch.SynchronousSGDOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.5)
    )
    opt.zero_grad()
    loss = model(torch.ones(1, 2)).sum()
    loss.backward()
    opt.step()
    np.testing.assert_allclose(
        model.weight.detach().numpy(), [[0.5, 0.5]], rtol=1e-6
    )


def test_all_reduce_tensor_single():
    from kungfu_tpu import torch as kf_torch

    t = torch.arange(6, dtype=torch.float32).view(2, 3)
    out = kf_torch.all_reduce(t)
    assert torch.equal(out, t)


@pytest.mark.parametrize("async_mode", ["", "on"])
def test_torch_e2e_two_workers(async_mode):
    """kfrun np=2: broadcast equalizes params, S-SGD keeps them
    bit-identical across ranks with rank-dependent data, PairAveraging
    contracts divergent models. Parametrized over KF_CONFIG_ASYNC: the
    "on" leg drives the async scheduler's optimizer step path (ISSUE
    10) — post-accumulate-grad hooks submit during backward from step 1
    on — and must land on the same cross-rank-identical params."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if async_mode:
        env["KF_CONFIG_ASYNC"] = async_mode
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", "2", "-H", "127.0.0.1:2",
            sys.executable, AGENT,
        ],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    oks = [l for l in r.stdout.splitlines() if "OK" in l]
    assert len(oks) == 2, r.stdout
    digests = {
        l.split("ssgd=")[1].strip()
        for l in r.stdout.splitlines() if "ssgd=" in l
    }
    assert len(digests) == 1, "S-SGD params differ across ranks"


def test_bf16_numpy_bridge_roundtrip():
    """torch bf16 crosses the numpy bridge by bit-reinterpretation (torch
    refuses .numpy() on bf16); _to_torch inverts it exactly."""
    from kungfu_tpu.torch import _flat_view, _to_torch

    t = torch.tensor([0.5, -1.25, 3.0, 65280.0], dtype=torch.bfloat16)
    v = _flat_view(t)
    assert v.dtype.itemsize == 2 and str(v.dtype) == "bfloat16"
    back = _to_torch(v)
    assert back.dtype == torch.bfloat16
    assert torch.equal(back, t)


def test_bf16_sync_and_allreduce_single():
    """bf16 params/grads work through sync_gradients and all_reduce
    (cluster of one: identity, but the whole bridge executes)."""
    from kungfu_tpu import torch as kf_torch

    model = torch.nn.Linear(3, 1, bias=False).to(torch.bfloat16)
    kf_torch.broadcast_parameters(model)
    loss = model(torch.ones(1, 3, dtype=torch.bfloat16)).sum()
    loss.backward()
    g0 = model.weight.grad.detach().clone()
    kf_torch.sync_gradients(model)
    assert torch.equal(model.weight.grad, g0)
    out = kf_torch.all_reduce(model.weight.detach())
    assert out.dtype == torch.bfloat16
    assert torch.equal(out, model.weight.detach())
