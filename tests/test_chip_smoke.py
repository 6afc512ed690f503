"""chip_smoke.py on the CPU: it refuses to run anywhere but on a TPU and
outside a checkout, and its phases pass at a tiny size with the Pallas
kernel in interpret mode (the test steers the constants the chip run
holds fixed)."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402

pytestmark = pytest.mark.tier1


def _run(script: Path, cwd: Path):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu", "HOME": str(cwd), "TMPDIR": str(cwd)}
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu_and_names_it(tmp_path):
    proc = _run(REPO / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_fails_without_the_program(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    proc = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert "no src/repro" in proc.stderr
    assert proc.stdout == ""


@pytest.fixture
def tiny(monkeypatch):
    """The chip run's phases at a CPU-sized fabric, kernel interpreted."""
    chip_smoke.import_repro()
    for name, value in {
            "PAPER_CLOS": dict(n_servers=16, n_tor=2, n_spine=2),
            "N_FLOWS": 60, "DRAIN": 3000,
            "PAPER_INCAST": dict(incast_degree=8, incast_total_kb=512),
            "KERNEL_IMPL": "interpret", "BUDGET_SOURCE": "host_meminfo",
            "KERNEL_MARK": "_fused_kernel"}.items():
        monkeypatch.setattr(chip_smoke, name, value)
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    monkeypatch.delenv("REPRO_KERNEL", raising=False)
    monkeypatch.delenv("REPRO_EXEC_MAX_BYTES", raising=False)
    return chip_smoke


def test_kernel_phase(tiny, capsys):
    tiny.phase_kernel()
    out = capsys.readouterr().out
    assert out.count("bit-identical to bfc_fused_ref") == 4


def test_main_phase(tiny, capsys):
    tiny.phase_main()
    out = capsys.readouterr().out
    assert out.count("lane fig6_incast/") == 3
    assert "bfc on interpret is bit-identical to lax" in out


def test_divergence_names_tick_and_channel():
    report = ("diff golden_dcqcn(run 0) vs dcqcn(run 0), lane 0\n"
              "  first divergence at tick 7 (3/2048 ticks differ)\n"
              "    occ        diverges at tick 7: [2] 4→5\n")
    assert chip_smoke.divergence(report) == (
        "first divergence at tick 7 (3/2048 ticks differ); "
        "occ        diverges at tick 7: [2] 4→5")
