#!/usr/bin/env python3
"""Checks of the yardstick itself, on the CPU, in about a minute:

    JAX_PLATFORMS=cpu python benchmarks/selfcheck.py

1. `trace_reduce.py` reduces the recorded v5e trace beside it
   (`fixtures/tpu_probe.xplane.pb`: 5 executions of a program launched
   under the host span ``engine.fused_segment`` and 5 of one launched
   under none, 0.6 s window) to the numbers read off it by hand.
2. `BENCHMARK.json` names only files that exist: every cell's traffic
   file, every configuration's file and query module, a reader for every
   per-layer metric.
3. Every cell is rehearsed through the bridge at the configuration's cut
   row count: the plan verifies, the window's results equal the pandas
   reference exactly, and the float32 control does not.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import control  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b))


def check_trace() -> None:
    r = trace_reduce.reduce_file(
        os.path.join(HERE, "fixtures", "tpu_probe.xplane.pb"))
    assert r["devices"] == 1
    assert close(r["window_s"], 0.600205138), r["window_s"]
    assert close(r["busy_s"], 0.000837507422), r["busy_s"]
    # 4 of the 5 executions of each program fall inside the window span
    assert r["launches"] == {"engine.fused_segment": 4, "[jit_other]": 5}, \
        r["launches"]
    assert close(r["scopes"]["engine.fused_segment"], 0.000810988828)
    assert close(r["programs"]["jit_seg"], r["scopes"]["engine.fused_segment"])
    assert close(r["ops"]["[jit_other]/sort_table"], 3.0342734e-05)
    top = trace_reduce.breakdown(r)
    assert top["device_ops"][0][0] == "engine.fused_segment/(reduce-window)"
    assert close(r["gaps"]["bench.idle"], 0.257531823)
    assert close(sum(r["gaps"].values()) + r["busy_s"], r["window_s"], 1e-6)
    print("selfcheck: the recorded trace reduces to the known numbers")


def check_files() -> list:
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    for m in bench["per_layer"]:
        assert callable(run.load_module("layer_metrics", m["name"]).read)
    cells = [run.Cell(w["name"], bench) for w in bench["workloads"]]
    for cell in cells:
        for fn in ("tables", "plan", "reference", "chunk_bytes_needed"):
            assert callable(getattr(cell.query, fn)), (cell.name, fn)
    print(f"selfcheck: {len(cells)} cells, {len(bench['per_layer'])} "
          "per-layer readers, every file found")
    return cells


def check_cells(cells: list) -> None:
    for cell in cells:
        result = run.run_cell(cell.name, seed=20, seconds=2.0, trace=False,
                              require_platform=None)
        assert result["correct"] and result["failed"] == 0, result
        r = control.readings(cell, 20, rehearsal=True)
        assert not r["control"]["correct"], r
        print(f"selfcheck: {cell.name}: {result['attempted']} results equal "
              "the reference; the float32 control reads a gap of "
              f"{r['control']['max_rel_gap']:.3g}")


def main() -> int:
    check_trace()
    check_cells(check_files())
    print(json.dumps({"selfcheck": "ok"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
