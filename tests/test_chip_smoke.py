"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size, and its
refusal to report anything without a TPU."""
import dataclasses
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = chip_smoke.SmokeConfig(n=400, avg_degree=8, dims=(16, 32, 32, 8),
                              batches=3, batch_edges=16, feat_rows=4,
                              read_rows=32)


@pytest.fixture(scope="module")
def workload():
    return chip_smoke.build_workload(TINY, log=lambda s: None)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_cpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_one_chip_phases_match_reference(workload):
    results = chip_smoke.one_chip_phases(workload, log=lambda s: None)
    assert [r.name for r in results] == ["a:device/xla-scatter",
                                         "b:device/pallas-delta_agg"]
    for r in results:
        assert r.ok, r
        assert r.max_abs < 1e-5
        assert r.reads_served == TINY.reads * (TINY.batches + 1)


def test_phase_fails_against_a_wrong_reference(workload):
    """The comparison has teeth: one reference row off by 0.01 fails it."""
    refs = dict(workload.refs)
    refs[TINY.batches] = refs[TINY.batches].copy()
    refs[TINY.batches][7] += 0.01
    bad = dataclasses.replace(workload, refs=refs)
    r = chip_smoke.run_phase("device", "device", bad, log=lambda s: None)
    assert not r.ok and r.max_abs >= 0.01 - 1e-6


def test_four_chip_phases_on_virtual_devices_subprocess():
    """The --chips 4 phases over four virtual CPU devices (the device count
    is fixed when jax starts, hence the fresh interpreter)."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        cfg = chip_smoke.SmokeConfig(n=400, avg_degree=8, dims=(16, 32, 32, 8),
                                     batches=3, batch_edges=16, feat_rows=4,
                                     read_rows=32)
        wl = chip_smoke.build_workload(cfg)
        res = chip_smoke.four_chip_phases(wl)
        assert [r.name for r in res] == ["sharded/4", "sharded_offload/4"]
        assert all(r.ok for r in res), res
        print("four-chip phases ok")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=ROOT, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "four-chip phases ok" in out.stdout
