"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
one-chip phases pass on the CPU at a tiny size with the Pallas kernels in
interpret mode (the same control flow, checks and references as on the
chip, only smaller)."""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
from repro.core import plan as plan_mod  # noqa: E402
from repro.kernels import scatter_ops  # noqa: E402


def test_chip_smoke_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.mark.parametrize("dep", [
    dataclasses.replace(cs.HOUSING, domains=dict(cs.HOUSING_SCALE, pc=512)),
    dataclasses.replace(cs.RETAILER, density=0.3, domains=dict(
        cs.RETAILER_SCALE, locn=4, dateid=4, ksn=8)),
], ids=["housing", "retailer"])
def test_served_phase_rehearses_on_cpu(monkeypatch, capsys, dep):
    monkeypatch.setattr(cs, "BATCH", 32)
    monkeypatch.setattr(cs, "N_ROUNDS", 2)
    monkeypatch.setattr(cs, "MIN_STATE_BYTES", 1)
    # interpret-mode kernels; chains whose terminal scatter resolves no
    # backend take the CPU's flat-XLA lowering
    monkeypatch.setattr(cs, "PALLAS_BACKENDS", frozenset({
        "onehot_interpret", "fused_interpret", "fused_xla"}))
    with scatter_ops.use_backend("onehot_interpret"), \
            plan_mod.use_fusion("on"):
        cs.served_phase(dataclasses.replace(dep, segment_updates=4), seed=3)
    out = capsys.readouterr().out
    assert "check vs numpy reference" in out
    assert "fused_interpret" in out
