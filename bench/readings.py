#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from.

    python bench/readings.py --workload det2-overload --seeds 1,2,3 \
        --seconds 10 --control 3

Per seed, builds and warms the cell's pod afresh, as a benchmark run
does, serves one short window at the cell's own traffic and sizes, and
prints the window's compared numbers (the program's readings, the lower
ends of the limits).  For the first
``--control`` seeds it also prints the control's numbers on the same
served sample: the reference one precision lower in the program's place
(``check.control_numbers``), the upper ends.  The benchmark's own runs
never run the control.  One JSON line per reading also goes to
``chiprun_out/readings_<workload>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent / "src"),
                str(Path(__file__).resolve().parent.parent)]

from bench import check, run as run_mod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("readings: JAX found no TPU; nothing was run", file=sys.stderr)
        return 2
    cell = run_mod.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = run_mod.ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    with open(out_dir / f"readings_{args.workload}.jsonl", "w") as f:
        for i, seed in enumerate(seeds):
            session = run_mod.Session(cell, seed, False)
            out = session.window(seed, args.seconds)
            row = {"seed": seed, "kind": "program", "correct": out["correct"],
                   "metrics": out["metrics"],
                   **{k: v["value"] for k, v in out["checks"].items()}}
            print("reading " + json.dumps(row))
            f.write(json.dumps(row) + "\n")
            if i < args.control:
                served, nms, params_ref = session.last
                check.diagnose(served, session.config, params_ref, print)
                ctl = check.control_numbers(served, nms, session.config,
                                            seed, params_ref)
                row = {"seed": seed, "kind": "control",
                       "correct": check.verdict(ctl,
                                                session.config["limits"]),
                       **{k: ctl[k] for k in check.NAMES}}
                print("reading " + json.dumps(row))
                f.write(json.dumps(row) + "\n")
            f.flush()
            del session
    return 0


if __name__ == "__main__":
    sys.exit(main())
