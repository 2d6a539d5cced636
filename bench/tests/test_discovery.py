"""A cell, a traffic mix and a metric are found by name: throwaway files
placed in a checkout run without a change to the harness."""

import json
import os

from bench import harness
from bench.tests.helpers import make_root, tiny_config

READER = '''
def read(run):
    return float(len(run.grids))
'''


def test_added_files_are_found(tmp_path, capsys):
    root = make_root(tmp_path, cells=[])
    cfg = tiny_config("quad48-bots", n_workers=4)
    cfg["apps"] = cfg["apps"][:1]
    with open(os.path.join(root, "bench", "configs", "extra.json"),
              "w") as f:
        json.dump(cfg, f)
    mix = dict(apps=["fib"], axes=dict(queue=["xqueue"], barrier=["tree"],
                                        balance=["static_rr", "na_ws"]),
               case_seeds=[[3], [4]], check_rows=2)
    with open(os.path.join(root, "bench", "traffic", "extra-mix.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "bench", "metrics", "grids_done.py"),
              "w") as f:
        f.write(READER)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(dict(name="extra", source="a test",
                                 file="bench/configs/extra.json",
                                 reduced=[], why="a test"))
    bench["workloads"] = [dict(name="extra.cell", config="extra",
                               traffic="extra-mix", chips=1, why="a test")]
    bench["end_to_end"].append(dict(name="grids_done", unit="grids",
                                    better="higher", bound=0.1,
                                    source="host_clock"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    rc = harness.main(["--workload", "extra.cell", "--seed", "3",
                       "--seconds", "0.5", "--trace", "0"], root=root,
                      require_accelerator=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["metrics"]["grids_done"]["value"] >= 1
    assert set(out["metrics"]) == {"rows_per_s", "setup_s", "grids_done"}
    assert out["attempted"] == 2 * out["metrics"]["grids_done"]["value"]
