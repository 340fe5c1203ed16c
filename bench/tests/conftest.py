"""The benchmark's own tests run on the CPU at small sizes:

    python -m pytest bench/tests -q
"""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

import pytest  # noqa: E402

from lib import harness  # noqa: E402

#: the cells shrunk to what a test holds: (O, J) and cycle ticks
SMALL = {"lustre248x4096": (8, 512)}
SMALL_CYCLE = {"online_filebench": 200, "replay_filebench": 200,
               "sweep_filebench": 100}


def small_checkout(tmp: Path) -> Path:
    """A copy of the benchmark's data files under ``tmp`` with every
    configuration and mix cut to test size; returns the benchmark root."""
    root = tmp / "bench"
    for kind in ("configs", "traffic", "profiles", "modes", "metrics"):
        shutil.copytree(BENCH / kind, root / kind)
    for name, (o, j) in SMALL.items():
        path = root / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(n_ost=o, n_jobs=j)
        path.write_text(json.dumps(cfg))
    for name, ticks in SMALL_CYCLE.items():
        path = root / "traffic" / f"{name}.json"
        tr = json.loads(path.read_text())
        tr["cycle_ticks"] = ticks
        if "check_rows" in tr:
            tr["check_rows"] = 6
        if "check_rounds_to" in tr:
            tr["check_rounds_to"] = 8
        path.write_text(json.dumps(tr))
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return root


@pytest.fixture
def small(tmp_path):
    """(benchmark dict, resolve(name) -> small Cell)."""
    root = small_checkout(tmp_path)
    bench = harness.load_json(tmp_path / "BENCHMARK.json")
    return bench, lambda name: harness.resolve(bench, name, repo=tmp_path,
                                               root=root)
