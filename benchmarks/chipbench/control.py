"""The readings that set a cell's limits, on the chip: the program's
compared numbers over many seeds (the lower readings) and the control's
(the reference computed at ``high`` precision in the program's place, on
the same sampled streams: the upper readings). One process runs every
seed; each seed is a whole run of the cell at the given window.

    python3 benchmarks/chipbench/control.py --workload <cell> \\
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds <s>

Prints one JSON line per seed with its compared numbers and, for a
control seed, the control's and ``control_correct``: the verdict of the
harness's own comparison with the control's numbers in the program's
place, against the configuration's limits (it has to be false).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import ROOT, TRACE_DIR  # noqa: E402,F401

import jax  # noqa: E402

from chipbench import catalog, driver  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    cell = catalog.cell(args.workload)
    cfg, mix = catalog.config(cell["config"]), catalog.traffic(cell["traffic"])
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        rec, check, _ = driver.run_cell(cfg, mix, seed, args.seconds, False,
                                        t0, TRACE_DIR, control=seed in ctl)
        line = {"seed": seed, "attempted": rec.attempted,
                "failed": rec.failed, **check,
                "correct": driver.verdict(rec, check, cfg["limits"])}
        if seed in ctl:
            line["control_correct"] = driver.verdict(
                rec, driver.control_numbers(check), cfg["limits"])
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
