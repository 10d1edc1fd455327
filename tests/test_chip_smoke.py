"""``chip_smoke.py`` on the CPU: every phase at the ``smoke`` size.

Only the device check needs a chip, and it is a separate function: the
tests call the phases directly, so each one runs exactly the code it
runs on the chip, at a size the CPU manages in seconds.  The check
itself is tested to refuse the CPU before any phase starts.
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def smoke():
    return chip_smoke.SIZES["smoke"]


@pytest.fixture(scope="module")
def profiled(smoke):
    return chip_smoke.phase_profiles(smoke)


def test_profiles_phase(profiled, capsys):
    w, session = profiled
    assert session.stats.profile_builds == len(chip_smoke.CORES)
    assert w.workload_name == chip_smoke.WORKLOAD


def test_exact_lru_phase(profiled, capsys):
    chip_smoke.phase_exact_lru(*profiled)
    assert "bit-identical" in capsys.readouterr().out


def test_service_phase(smoke, profiled, capsys):
    chip_smoke.phase_service(smoke, profiled[1])
    assert "responses bit-identical" in capsys.readouterr().out


def test_sweep_phase(smoke, profiled, capsys):
    chip_smoke.phase_sweep(smoke, *profiled)
    assert "vmap bit-identical" in capsys.readouterr().out


def test_full_size_sweeps_ten_thousand_configs():
    from repro.explore import SearchSpace

    full = chip_smoke.SIZES["full"]
    space = SearchSpace(target=chip_smoke.LRU_TARGET, level="L3",
                        sets=full.sets, ways=full.ways,
                        latency_cy=full.latency_cy, beta_cy=full.beta_cy,
                        cores=chip_smoke.CORES)
    assert space.size == 10_000
    assert full.preset == "validation-xxl"


def test_no_chip_fails_before_any_phase(capsys):
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke.main()
    assert capsys.readouterr().out == ""


def test_script_alone_fails(tmp_path):
    """Copied into a directory with nothing else of the repo, the
    script exits non-zero and prints no result."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
