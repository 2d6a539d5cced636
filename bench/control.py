"""Readings that set the limit of the row check, for one cell, in one
process:

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ...

For each seed it runs the cell's first timed grid through the program, as a
run of ``bench/run.py`` would, draws the check's sample of rows, and counts
the fields in which the sample differs from the reference (the lower
reading: sound runs of the program), then the fields in which the control
differs from the reference.  The control is the reference put in the
program's place with its float32 execution-penalty arithmetic computed in
bfloat16.  One JSON line per seed, then a summary.
``--host`` allows a run without an accelerator (the tests use it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(root: str, cell_name: str, seeds, host: bool = False) -> list:
    sys.path[:0] = [root, os.path.join(root, "src")]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[cell_name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    import jax

    if not host and jax.devices()[0].platform == "cpu":
        raise SystemExit("control: needs an accelerator (or --host)")
    from bench import check
    from bench import graphs as graphs_mod
    from bench.grid import GridSource
    from bench.harness import GridRun, Program, use_compile_cache
    from bench.reference import sim

    use_compile_cache(root)
    graphs = [graphs_mod.build(a, config["graph_seed"])
              for a in config["apps"]]
    program = Program(config, graphs)
    n = traffic.get("check_rows", 12)
    out = []
    for seed in seeds:
        cases = GridSource(traffic, config, seed).next()  # first timed grid
        res = program.run(program.specs(cases))
        grids = [GridRun(cases=cases, specs=None, result=res, seconds=0.0)]
        prog_bad = ctrl_bad = ctrl_rows = 0
        for g, i in check.sample(grids, n, seed):
            case = cases[i]
            args = (graphs[case["graph"]], case, config["sim"],
                    config.get("machine"), config["costs"])
            ref = sim.simulate(*args)
            prog_bad += len(check.mismatches(check.program_row(res, i), ref))
            ctl = sim.simulate(*args, exec_float="bfloat16")
            bad = check.mismatches(ctl, ref)
            ctrl_bad += len(bad)
            ctrl_rows += bool(bad)
        rec = dict(seed=seed, program_mismatched_fields=prog_bad,
                   control_mismatched_fields=ctrl_bad,
                   control_mismatched_rows=ctrl_rows)
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--host", action="store_true")
    args = ap.parse_args(argv)
    recs = readings(ROOT, args.workload, args.seeds, host=args.host)
    print(json.dumps(dict(
        workload=args.workload, seeds=len(recs),
        program_max_mismatched_fields=max(
            r["program_mismatched_fields"] for r in recs),
        control_min_mismatched_fields=min(
            r["control_mismatched_fields"] for r in recs))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
