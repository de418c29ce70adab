"""A configuration, a traffic mix and a per-layer metric that a later
change adds as files are found by their names, with no edit to a file
that is already there."""

import json
import os
import shutil

from benchmark import harness, spec
from benchmark.tests import tiny

NEW_METRIC = '''
def read(ctx):
    return float(sum(o.rows for o in ctx.window.outcomes))
'''


def test_new_files_are_found_by_name(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(spec.ROOT, "benchmark", "configs",
                           "sift1m-ivf_flat.json")) as f:
        conf = json.load(f)
    conf["name"] = "tiny-ivf_flat"
    conf["search"]["n_probes"] = 4
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-ivf_flat.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "trickle.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 1, "rows_per_request": 8,
                   "deadline_ms": 60000}, f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "rows_sent.trickle.py"), "w") as f:
        f.write(NEW_METRIC)
    bench["configs"].append({"name": "tiny-ivf_flat", "source": "test",
                             "file": "benchmark/configs/tiny-ivf_flat.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-ivf_flat.trickle",
                               "config": "tiny-ivf_flat",
                               "traffic": "trickle", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "rows_sent.trickle", "unit": "rows",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "qps",
                               "workloads": ["tiny-ivf_flat.trickle"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = spec.load_cell("tiny-ivf_flat.trickle", root)
    assert cell.config["search"]["n_probes"] == 4
    assert cell.traffic["rows_per_request"] == 8
    assert [m["name"] for m in cell.per_layer] == ["rows_sent.trickle"]
    assert {m["name"] for m in cell.end_to_end} == {"recall_at_10",
                                                    "setup_s"}
    r = harness.run("tiny-ivf_flat.trickle", tiny.SEED, 0.5, True,
                    root=root, require_chip=False, cell=tiny.shrink(cell))
    assert r["correct"], r["checks"]
    assert r["metrics"]["rows_sent.trickle"]["value"] == 8 * r["attempted"]
    assert harness.warm_buckets((1, 8, 64, 512), cell.traffic) == [8]
