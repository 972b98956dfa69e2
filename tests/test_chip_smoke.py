"""``chip_smoke.py`` off the chip: it refuses to run on the CPU, and its
phases pass at small sizes here (the CPU rehearsal of the chip run).

The phases are called directly, at a few tenants and counters, with
Pallas kernels in interpret mode; the script's own entry point only
ever runs on a TPU.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, cwd, extra_env=None, code=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    env.update(extra_env or {})
    cmd = [sys.executable] + (["-c", code] if code else [str(args)])
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_refuses_the_cpu():
    out = _run(SCRIPT, REPO)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_fails_alone(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert out.returncode != 0
    assert "not next to this script" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("tenants", [64, 1024])
def test_service_phase_small(tenants):
    # 1024 tenants is past bank.touched_chunk_rows(512, 128) = 64: blocks
    # take the touched-rows ingest, one chunk or several, as at the
    # chip's 16,384
    res = _load().service_phase(tenants=tenants, k_per_tenant=128,
                                block=512, n_updates=20_000, spill_after=2)
    st = res["stats"]
    assert st["spills"] > 0 and st["admits"] > 0 and st["blocks"] > 10
    assert len(res["sample"]) == 16


def test_kernel_phase_small():
    res = _load().kernel_phase(shapes=((8, 128),), block=256, n_blocks=2,
                               bits=12)
    assert res["shapes"] == [(8, 128)]


def test_mesh_phase_on_four_host_devices():
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('cs', {str(SCRIPT)!r})\n"
        "cs = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(cs)\n"
        "print(cs.mesh_phase(n_devices=4, k_per_shard=32, bits=14, "
        "block=512, n_inserts=4000))\n")
    out = _run(None, REPO, code=code, extra_env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "bit_identical=True" in out.stdout
    assert "[0, 1, 2, 3]" in out.stdout
