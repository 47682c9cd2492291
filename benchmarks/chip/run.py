#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout. The cell is an entry of ``BENCHMARK.json``;
its configuration, traffic mix and correctness limits are data files under
``benchmarks/chip`` found by name. The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last the compared numbers under
``checks``); the compared numbers are also the last lines of standard error.

The run exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for, or when the repository's program is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: no program under {ROOT / 'src'}; nothing was run",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(CHIP_DIR)]
    from chipbench import harness, workload

    cell = workload.load_cell(args.workload, ROOT)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    if jax.default_backend() != "tpu" or len(devs) < cell.chips:
        print(f"run.py: the cell needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {jax.default_backend()} device(s); nothing was "
              f"measured", file=sys.stderr)
        return 3
    enable_compile_cache()
    # every program, eager ops included, goes to the persistent cache, so
    # only a checkout's first run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
