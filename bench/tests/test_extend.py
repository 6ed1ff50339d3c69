"""A later change adds a cell, a driver and a per-layer metric as new
files plus new entries, and edits no file that is already there."""
import hashlib
import json
import os
import shutil

import harness

ROOT = os.path.dirname(harness.BENCH)


def _digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_add_cell_driver_and_metric(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digest(root)

    # new files: a traffic mix for a new driver, the driver, a reader
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "traffic", "echo-b64.json"), "w") as f:
        json.dump({"driver": "echo", "job": {"protocol": "vanilla", "R": 0,
                                             "batch": 64}}, f)
    with open(os.path.join(b, "drivers", "echo.py"), "w") as f:
        f.write("class Run:\n    rows_per_step = 64\n")
    with open(os.path.join(b, "metrics", "echo_ms.samples.py"), "w") as f:
        f.write("def read(rec):\n    return rec['window']['seconds'] * 1e3\n")

    # new entries in BENCHMARK.json
    p = os.path.join(root, "BENCHMARK.json")
    with open(p) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "wdl-criteo.echo",
                               "config": "wdl-criteo", "traffic": "echo-b64",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_samples_per_s":
            m["workloads"].append("wdl-criteo.echo")
    bench["per_layer"].append({"name": "echo_ms.samples", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "host loop",
                               "moves": "train_samples_per_s",
                               "workloads": ["wdl-criteo.echo"]})
    with open(p, "w") as f:
        json.dump(bench, f)

    cell = harness.Cell("wdl-criteo.echo", root=root)
    assert cell.driver.Run.rows_per_step == 64
    assert cell.family.GENERATOR == "tabular"
    rec = {"window": {"seconds": 2.0, "rows": 128, "steps": 2,
                      "tokens": None}, "setup_s": 1.5}
    e2e = harness.read_metrics(cell, rec, trace=False)
    assert e2e == {"train_samples_per_s": {"value": 64.0,
                                           "unit": "samples/s"},
                   "setup_s": {"value": 1.5, "unit": "s"}}
    layer = harness.read_metrics(cell, rec, trace=True)
    assert layer == {"echo_ms.samples": {"value": 2000.0, "unit": "ms"}}

    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
