"""A benchmark root at test size: BENCHMARK.json naming tiny cells, with
their configuration, traffic and limit files added as data alone; the
metric readers are the benchmark's own."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
DATA = HERE / "data"

CELLS = {
    "zamba2-tiny.t4-s64": ("zamba2-tiny", "t4-s64", 1),
    "mamba2-tiny.t4-s64": ("mamba2-tiny", "t4-s64", 1),
    "zamba2-tiny.dp4-1bit-t4-s64": ("zamba2-tiny", "dp4-1bit-t4-s64", 4),
    "zamba2-tiny.dp4-int8-t4-s64": ("zamba2-tiny", "dp4-int8-t4-s64", 4),
}


def make_root(tmp: Path) -> Path:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [
        {"name": n, "source": "test", "file": f"chipbench/configs/{n}.json",
         "reduced": [], "why": "test size"}
        for n in sorted({c for c, _, _ in CELLS.values()})]
    bench["workloads"] = [
        {"name": k, "config": c, "traffic": t, "chips": n, "why": "test"}
        for k, (c, t, n) in CELLS.items()]
    (tmp / "chipbench").mkdir(parents=True)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(REPO / "chipbench" / "metrics", tmp / "chipbench" /
                    "metrics")
    for sub in ("configs", "traffic", "limits"):
        shutil.copytree(DATA / sub, tmp / "chipbench" / sub)
    return tmp
