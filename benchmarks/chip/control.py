#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program's widest
served-token gap and the control's, on several seeds in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s>

Each seed is a full run of the cell (its own weights, warm-up and window of
``--seconds``), checked against the plain reference. Each control, the
reference one precision step lower in some part (``chipbench/reference.py``),
is then put in the program's place on the same sample and judged by the
same checks and limit, so a sound control line reads ``"correct": false``.
One JSON line per seed: the program's gap and verdict, each control's gap
and verdict, and the limit. The benchmark's runs never do this; it is the
measurement ``limits/<cell>.json`` records. Needs the TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(CHIP_DIR)]
    from chipbench import harness, workload

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    if jax.default_backend() != "tpu":
        print("control.py: JAX found no TPU; nothing was run", file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = workload.load_cell(args.workload, ROOT)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        res = harness.run(cell, seed, args.seconds, False, t_start=t0,
                          with_control=True)
        print(json.dumps({
            "seed": seed, "limit": res["checks"]["max_logit_gap"]["limit"],
            "program": {"correct": res["correct"],
                        "max_logit_gap": res["checks"]["max_logit_gap"]["value"]},
            "controls": res["controls"], "metrics": res["metrics"],
            "window": res["window"], "seconds": time.perf_counter() - t0}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
