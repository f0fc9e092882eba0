"""CPU rehearsal of the harness at a tiny graph, through its functions
(``run_cell`` with the chip look switched off), not its TPU-only CLI."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
TINY_CONFIG = {"graph": {"n": 400, "m": 4, "pool_edges": 300},
               "dims": [16, 32, 32, 8]}
TINY_MIX = {"update_rate_per_s": 30, "read_rate_per_s": 40, "read_rows": 16,
            "batch_cap_events": 16,
            "warmup": [{"events": 32, "features": True},
                       {"events": 32, "features": False}]}
# metrics that only a device can give: absent from any CPU run
DEVICE_ONLY = {m["name"] for m in SPEC["per_layer"]
               if m["source"] == "device_trace"} | {"step_mfu"}


def tiny_run(cell, seed=2**33 + 5, trace=False, root=ROOT, mix=None,
             config_overrides=TINY_CONFIG, log=lambda s: None, **kw):
    return harness.run_cell(cell, seed, 1.5, trace, root=root, require_tpu=False,
                            config_overrides=config_overrides,
                            mix_overrides={**TINY_MIX, **(mix or {})},
                            log=log, **kw)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_and_reports_every_metric(cell, trace):
    out = tiny_run(cell, trace=bool(trace))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in SPEC[kind]
             if "workloads" not in m or cell in m["workloads"]}
    assert set(out["metrics"]) == names - DEVICE_ONLY
    assert out["device"]["platform"] == "cpu"
    assert "busy_s" not in out["device"] and "breakdown" not in out
    for m in out["metrics"].values():
        assert np.isfinite(m["value"]) and m["unit"]


def _corrupt_state(monkeypatch):
    from repro.core.backend import DeviceBackend

    orig = DeviceBackend._dispatch_packed

    def dispatch(self, packed):
        orig(self, packed)
        self._h[-1] = self._h[-1].at[5].add(0.5)

    monkeypatch.setattr(DeviceBackend, "_dispatch_packed", dispatch)


def _state_unchanged(monkeypatch):
    from repro.core.backend import DeviceBackend

    monkeypatch.setattr(DeviceBackend, "_dispatch_packed", lambda self, p: None)


def _half_batch(monkeypatch):
    from repro.serve.frontend import ServingFrontend

    orig = ServingFrontend.apply_batch

    def apply_batch(self, b):
        k, j = b.ins_src.size // 2, b.del_src.size // 2
        b.ins_src, b.ins_dst = b.ins_src[:k], b.ins_dst[:k]
        b.del_src, b.del_dst = b.del_src[:j], b.del_dst[:j]
        return orig(self, b)

    monkeypatch.setattr(ServingFrontend, "apply_batch", apply_batch)


def _wrong_version(monkeypatch):
    from repro.serve.frontend import ServingFrontend

    monkeypatch.setattr(ServingFrontend, "_reconstruct",
                        lambda self, rows, pin: np.array(
                            self._orch.backend.snapshot_rows(rows)))


def _altered_answer(monkeypatch):
    from repro.core.backend import DeviceBackend

    orig = DeviceBackend.snapshot_rows

    def snapshot_rows(self, rows):
        out = np.array(orig(self, rows))
        out[0] += 0.5
        return out

    monkeypatch.setattr(DeviceBackend, "snapshot_rows", snapshot_rows)


FAULTS = {
    "state_unchanged": (_state_unchanged, "final_rms"),
    "half_batch_left_out": (_half_batch, "final_rms"),
    "corrupted_embedding_row": (_corrupt_state, "final_rms"),
    "read_from_wrong_version": (_wrong_version, "read_err"),
    "altered_answer": (_altered_answer, "read_err"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_makes_correct_false(fault, monkeypatch):
    plant, number = FAULTS[fault]
    plant(monkeypatch)
    out = tiny_run(CELLS[0], mix={"pinned_share": 0.5})
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits_at_full_widths(cell):
    """The reference one precision step down (three bfloat16 passes), in
    the program's place, fails a limit that the program meets."""
    cfg = {"graph": {"n": 800, "m": 7, "pool_edges": 300}}
    mix = {"update_rate_per_s": 10, "read_rate_per_s": 10, "read_rows": 64,
           "batch_cap_events": 8, "pinned_share": 0.3,
           "warmup": [{"events": 16, "features": True}]}
    out = harness.run_cell(cell, 7, 1.0, False, require_tpu=False,
                           config_overrides=cfg, mix_overrides=mix,
                           control=True, log=lambda s: None)
    assert not out["correct"], out["checks"]
    checks, program = out["checks"], out["program"]
    assert any(checks[k]["value"] > checks[k]["limit"]
               for k in ("final_rms", "read_err")), checks
    assert all(program[k] <= checks[k]["limit"]
               for k in ("final_rms", "read_err")), (program, checks)


def test_due_reads_are_served_together():
    """Reads that fall due while a batch runs are submitted together and
    answered by one ``serve_reads`` call, each checked at its version."""
    lines = []
    out = tiny_run(CELLS[0], mix={"read_rate_per_s": 400, "pinned_share": 0.3},
                   log=lines.append)
    assert out["correct"], out["checks"]
    served = [s for s in lines if s.startswith("reads: ")]
    assert served, lines
    largest = int(served[-1].rsplit(" ", 1)[-1])
    assert largest > 1, served


def test_configured_dtype_is_enforced():
    with pytest.raises(ValueError, match="configuration states"):
        tiny_run(CELLS[0], config_overrides={**TINY_CONFIG, "dtype": "bfloat16"})


def _bench_copy(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    return tmp_path


def test_a_new_mix_and_metric_need_no_edit_to_existing_files(tmp_path):
    root = _bench_copy(tmp_path)
    (root / "chipbench" / "mixes" / "slow-test.json").write_text(json.dumps(
        {**json.loads((ROOT / "chipbench" / "mixes" / "live14.json").read_text()),
         "update_rate_per_s": 5}))
    (root / "chipbench" / "metrics" / "events_due_test.py").write_text(
        "def read(rec):\n    return float(rec.ev_due.size)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "gcn3-arxiv.slow-test", "config": "gcn3-arxiv",
                              "traffic": "slow-test", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "events_due_test", "unit": "count",
                              "better": "lower", "source": "host_clock",
                              "layer": "serving", "moves": "read_p95_s",
                              "workloads": ["gcn3-arxiv.slow-test"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = harness.run_cell("gcn3-arxiv.slow-test", 3, 1.5, True, root=root,
                           require_tpu=False, config_overrides=TINY_CONFIG,
                           mix_overrides={k: v for k, v in TINY_MIX.items()
                                          if k != "update_rate_per_s"},
                           log=lambda s: None)
    assert out["correct"]
    assert out["metrics"]["events_due_test"]["value"] > 0
    assert "events_due_test" not in tiny_run(CELLS[0], trace=True)["metrics"]


def test_unknown_device_kind_raises():
    assert harness.load_peaks(ROOT / "chipbench", "TPU v5 lite")["bf16_flops_per_s"] > 0
    with pytest.raises(KeyError, match="no published peaks"):
        harness.load_peaks(ROOT / "chipbench", "TPU v99")


def test_run_refuses_without_a_tpu(capsys):
    from chipbench import run

    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_checkout_of_benchmark_files_alone_exits_nonzero(tmp_path):
    root = _bench_copy(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                          CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
                         cwd=root, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
