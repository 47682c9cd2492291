"""The harness on the CPU at a tiny size: the window cut leaves every
request terminal and no page leaked, warm-up leaves nothing to compile in a
backlog's window, the reference passes the program and fails its controls,
and the measurement path refuses a machine without the chip."""
from __future__ import annotations

import json
import shutil
import subprocess
import sys
import time

from conftest import CHIP_DIR, ROOT, TINY_LIMIT, tiny_traffic

from chipbench import harness, reference


def run(cell, seed=3_000_000_007, seconds=1.5, **kw):
    return harness.run(cell, seed, seconds, False, t_start=time.perf_counter(),
                       backend="reference", **kw)


def test_backlog_window_cut(tiny_cell):
    res = run(tiny_cell(tiny_traffic("lbim")))
    checks = {k: c["value"] for k, c in res["checks"].items()}
    assert res["correct"], checks
    assert checks["requests_not_terminal"] == 0
    assert checks["pool_invariant_violations"] == 0
    assert res["window"]["compiles"] == 0
    assert res["window"]["compared_tokens"] > 0
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tokens_per_s", "tpot_p95_ms",
                                   "peak_hbm_gb", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert sum(res["window"]["steps_per_slice"]) > 0
    assert list(res)[-1] == "checks"


# At this size int8 KV alone and int8 prefill projections alone read as low
# as the program itself (gaps 0-0.08 against its 0.03-0.08 on four seeds,
# CPU); the W4A4 decode projections, alone or with the rest, read 1.0-2.7.
CONTROLS_THAT_FAIL_TINY = ("w4a4", "all")


def test_controls_judged_by_the_same_check_read_incorrect(tiny_cell):
    """Each control, put in the program's place on the same sample, goes
    through the run's own comparison and limit; the W4A4 ones come out not
    correct, while the program's run beside them comes out correct."""
    res = run(tiny_cell(tiny_traffic("lbim")), seed=3_000_000_013,
              with_control=True)
    assert res["correct"], res["checks"]
    assert set(res["controls"]) == set(reference.CONTROLS)
    for name in CONTROLS_THAT_FAIL_TINY:
        c = res["controls"][name]
        assert c["max_logit_gap"] > TINY_LIMIT, (name, c)
        assert c["correct"] is False, (name, c)


def test_refuses_a_machine_without_the_chip(capsys):
    sys.path.insert(0, str(CHIP_DIR))
    import run as entry

    rc = entry.main(["--workload", "yi9b-16l.chat-lbim-b4", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "TPU" in out.err


def test_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for rel in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                        "yi9b-16l.chat-lbim-b4", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
