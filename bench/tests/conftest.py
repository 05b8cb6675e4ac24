"""CPU fixtures for the benchmark's own tests: the tiny configuration and
traffic mix in ``data/`` stand in for the real cells."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

TINY_CELL = "tiny.chat"


class FakeChip:
    """Stands in for the chip where a test skips the harness's look for
    one: the peaks table is keyed by this kind."""
    platform = "cpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return None


@pytest.fixture
def tiny(monkeypatch):
    import cell
    import traffic

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-moe",
                         "file": "bench/tests/data/tiny-moe.json"}]
    bench["workloads"] = [{"name": TINY_CELL, "config": "tiny-moe",
                           "traffic": "tiny-chat", "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    monkeypatch.setattr(cell, "load_benchmark", lambda: bench)
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", DATA)
    return cell
