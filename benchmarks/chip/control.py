"""The control of the comparison that decides ``correct``, on the chip.

For each seed, one run of the cell at its own size and load (set-up,
window, comparison), then the same comparison with the reference itself
put in the program's place, computed in bfloat16 (``check.py``).  It
prints both readings of every number compared, the program's (the lower
reading) and the control's (the upper reading), which the limits in the
configuration files are set between.  The benchmark's own runs do not
run it.

    python3 benchmarks/chip/control.py --workload housing.stream \\
        --seconds 30 --seeds 11 12 13
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from benchmarks.chip import check, harness  # noqa: E402


def readings(cfg: dict, out: dict) -> dict:
    """The program's numbers and the control's, from one run."""
    ev = out["evidence"]
    ref_cls = harness.part("references", cfg["name"]).Reference
    ctl = check.compare(cfg, ref_cls, ev["base0"], ev["batches"], ev["reads"],
                        ev["final_views"], ev["final_base"],
                        answers="control")
    ctl["reads_missing"] = 0
    return {"program": out["numbers"], "control": ctl}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell, cfg, traffic = harness.cell_files(bench, args.workload)

    import jax

    harness.use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("the control runs on the chip", file=sys.stderr)
        return 2
    t = T_PROCESS
    for seed in args.seeds:
        out = harness.run_cell(cfg, traffic, seed, args.seconds, False, t,
                               log=lambda s: print(s, file=sys.stderr))
        r = readings(cfg, out)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"], **r}), flush=True)
        del out
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
