"""CPU rehearsal of chip_smoke.py: its phases at a tiny scale, and the
contract that it refuses to run anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys

import pytest
from test_multidevice import run_with_devices

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(scale=10, batch_size=32)


def test_one_chip_phases_at_tiny_scale(capsys):
    chip_smoke.run_one_chip(**TINY)
    phases = [line.split(":")[0] for line in capsys.readouterr().out.splitlines()]
    assert phases == [
        "phase preprocess.order",
        "phase preprocess.commit",
        "phase stream",
        "phase query.pagerank",
        "phase query.sssp",
        "phase query.wcc",
        "phase kernel.segment_rf",
    ]


def test_four_chip_phases_on_four_host_devices():
    out = run_with_devices(
        f"import chip_smoke; chip_smoke.run_four_chip(**{TINY!r})", n=4
    )
    assert "phase rescale.8-12-8" in out and "phase stream:" in out
    held = next(line for line in out.splitlines() if line.startswith("phase pack.sharded"))
    assert held.count(":") == 5  # the phase and four devices, each holding rows


@pytest.mark.parametrize("alone", [False, True])
def test_script_exits_nonzero_without_a_tpu(tmp_path, alone):
    """On the CPU, and in a directory holding chip_smoke.py and nothing else
    of the repo, the script fails and prints no result line."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        env=env, cwd=tmp_path, timeout=300,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
