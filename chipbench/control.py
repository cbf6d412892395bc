"""The control of ``correct``: the plain reference computed in float32, put
in the program's place, read by the same comparison at a cell's own size.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 --tasks <n>

``--tasks`` is the number of tasks a run of the cell serves (its
``attempted``). For each seed it prints the compared numbers of the float32
reference against the float64 one, each beside its limit, and whether the
comparison refuses it (it must). The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from harness import arrivals, compare, models, reference, spec  # noqa: E402


def readings(root: Path, cell_name: str, seed: int, n: int) -> dict:
    bench = spec.load(root)
    cell = spec.cell(bench, cell_name)
    cfg = spec.config(root, bench, cell["config"])
    traffic = spec.traffic(root, cell["traffic"])
    proc = dict(traffic["process"])
    if proc.get("rate_per_s") is None:
        proc["rate_per_s"] = cfg["app_spec"]["arrival_rate_per_s"]
    tables = models.fit_deployment(cfg)
    if traffic["kind"] == "replay":
        step = int(traffic["chunk_rows"])
        scales = [float(x) for x in traffic["warm_rate_scale"]]
        rows = [step] * len(scales)
    else:
        rows = [int(r) for r in traffic["warm_rows"]]
        step, scales = rows[0], [1.0] * len(rows)
    parts, stream = arrivals.warm_and_window(
        cfg["app_spec"], proc, seed, rows, scales, traffic.get("warm_seed"))
    done = sum(rows)
    while done < n:
        parts.append(stream.block(min(step, n - done)))
        done += step
    arr, size, nb = (np.concatenate([p[i] for p in parts]) for i in range(3))
    arr = arr + cfg["stream_start_ms"]
    names = reference.target_names(cfg)
    ref = reference.serve(cfg, tables, seed, arr, size, nb)
    ctl = reference.serve(cfg, tables, seed, arr, size, nb, dtype=np.float32)
    return compare.readings(compare.as_served(ctl, names), ref, names)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tasks", type=int, required=True)
    args = ap.parse_args(argv)
    refused = True
    for seed in (int(s) for s in args.seeds.split(",")):
        got = readings(HERE.parent, args.workload, seed, args.tasks)
        ok, rows = compare.verdict(got)
        refused &= not ok
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "tasks": args.tasks, "control_correct": ok,
                          "checks": {k: {"value": v, "limit": lim}
                                     for k, v, lim in rows}}))
    return 0 if refused else 1


if __name__ == "__main__":
    sys.exit(main())
