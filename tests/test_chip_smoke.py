"""``chip_smoke.py`` on CPU: its phases at the reduced config, and its guard.

The phases run with Pallas kernels interpreted (or on the jnp oracle above
the interpreter's size cap), so they check control flow and the phases' own
comparisons, not the chip. The script itself must refuse to report success
anywhere but on a TPU.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = REPO / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_train_phase_reduced(smoke):
    res = smoke.train_phase(reduced=True, cut=None)
    assert res["problems"] == []
    on = res["on"]
    assert len(on["losses"]) == smoke.TRAIN["steps"]
    assert on["report"]["megakernel"] == "fused"
    assert on["first_step_s"] > 0 and on["steady_step_s"] > 0


def test_serve_phase_reduced(smoke):
    res = smoke.serve_phase(reduced=True, cut=None)
    assert res["problems"] == []
    # The reduced config computes in float32: both comparisons are exact.
    assert res["differences"] == {"bf16": {}, "f32": {}}
    paged = res["runs"]["paged"]
    assert len(paged["tokens"]) == smoke.REQUESTS
    assert paged["report"]["decisions"]["paged_attention"].split()[0] \
        in ("pallas-interpret", "ref")


def test_kernel_faults_flags_uncompiled_backends(smoke):
    faults = smoke.kernel_faults({
        "stale_accum": "pallas",
        "fused_update": "pallas-interpret",
        "fused_adam": "ref (interpret mode, operand over 262144 elems)"})
    assert faults == ["fused_update -> pallas-interpret",
                      "fused_adam -> ref (interpret mode, operand over "
                      "262144 elems)"]


@pytest.mark.parametrize("case", ["cpu", "interpret", "alone"])
def test_script_refuses_without_tpu(case, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "REPRO_KERNELS_INTERPRET")}
    env["JAX_PLATFORMS"] = "cpu"
    script, cwd = SCRIPT, REPO
    if case == "interpret":
        env["REPRO_KERNELS_INTERPRET"] = "1"
    elif case == "alone":   # a directory that holds only the script
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
        cwd = tmp_path
    proc = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    if case == "interpret":
        assert "REPRO_KERNELS_INTERPRET" in proc.stdout
