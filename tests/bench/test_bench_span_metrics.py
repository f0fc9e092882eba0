"""The readers of the metrics taken from the program's spans and counters,
on synthetic records: the mean over the window's batches, and nothing where
there are no batches or the program keeps no such field."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402

# metric → (BatchStats field, scale of the reading)
READS = {"pack_ms": ("pack_time_s", 1e3),
         "undo_capture_ms": ("hook_time_s", 1e3),
         "h2d_bytes_per_batch": ("h2d_bytes", 1),
         "batch_compile_ms": ("compile_time_s", 1e3)}


def _reader(name):
    return harness.load_module(ROOT / "chipbench" / "metrics" / f"{name}.py").read


def _record(stats):
    batches = [(bs, 2 * k, 2 * k + 2, float(k), k + 0.5)
               for k, bs in enumerate(stats)]
    z = np.zeros(0)
    return harness.Record(setup_s=1.0, seconds=10.0, ev_due=z, ev_visible=z,
                          rd_due=z, rd_submitted=z, rd_served=z,
                          batches=batches, serve_calls=[], compiles=0,
                          device={})


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_is_the_mean_over_the_batches(name):
    from repro.core.backend import BatchStats

    field, scale = READS[name]
    vals = [0.25, 0.5, 2.0] if scale != 1 else [1000, 3000, 8000]
    stats = [BatchStats(1, 0, 1, 0.0, 0.0, 0.0, **{field: v}) for v in vals]
    assert _reader(name)(_record(stats)) == pytest.approx(
        scale * sum(vals) / len(vals))


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_gives_nothing_without_batches_or_the_field(name):
    read = _reader(name)
    assert read(_record([])) is None
    # a program without the span (its BatchStats lacks the field)
    older = SimpleNamespace(inc_edges=1, full_edges=0, plan_time_s=0.1,
                            graph_time_s=0.1, exec_time_s=0.1)
    assert read(_record([older, older])) is None


def test_every_new_metric_is_declared_for_both_cells():
    import json

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in spec["workloads"]]
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in READS:
        assert declared[name]["workloads"] == cells
        assert declared[name]["moves"] == "update_visible_p95_s"
