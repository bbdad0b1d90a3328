"""A later PR adds a cell, a configuration, a traffic mix and a per-layer
metric as new files and new entries only. ``tiny.write`` already does so
for cells (a directory of its own ahead of ``benchmarks/`` in ``paths``);
here one more per-layer metric is added the same way and read."""

import json
import os
import time

import tiny
from lib import cells, harness

READER = '''"""sweep.jobs_in_window (count): whole jobs the window held."""


def read(obs):
    return float(obs.stats.jobs)
'''


def test_extra_cell_and_metric_from_new_files_only(tmp_path):
    bench_file = tiny.write(str(tmp_path))
    with open(bench_file) as f:
        bench = json.load(f)
    os.makedirs(tmp_path / "extra" / "layer_metrics")
    (tmp_path / "extra" / "layer_metrics" / "sweep.jobs_in_window.py").write_text(READER)
    bench["per_layer"].append({
        "name": "sweep.jobs_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry point / harness",
        "moves": "schedules_per_s", "workloads": ["tiny-sweep"],
    })
    with open(bench_file, "w") as f:
        json.dump(bench, f)

    cell = cells.load_cell(bench_file, "tiny-sweep")
    assert cell.config["workload"]["nodes"] == 3          # the temporary file's
    assert cell.traffic["job"]["schedules"] == 96
    assert cells.find_file(cell.root, cell.bench, "verbs/sweep.py").startswith(tiny.BENCH)

    result = harness.run(
        bench_file, "tiny-sweep", 5, 0.3, True, time.perf_counter(),
        require_tpu=False, log=lambda _line: None,
    )
    assert result["correct"] is True
    assert result["metrics"]["sweep.jobs_in_window"]["value"] >= 1
    # the real benchmark's files were not touched
    assert not os.path.exists(os.path.join(tiny.BENCH, "layer_metrics", "sweep.jobs_in_window.py"))
