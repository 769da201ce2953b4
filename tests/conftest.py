"""Test-size stand-ins for the benchmark cells whose traffic and
configuration files came after ``tests/bench``'s tables
(``bench_helpers.SMALL_TRAFFIC`` and ``SMALL_CONFIG``): the helpers
look each cell's up by name, so every cell of ``BENCHMARK.json`` needs
one.  They are registered here, where every run of the tests, of
``tests/bench`` alone too, loads them first.

The way-partitioned whole-frame cell is cut as the unpartitioned one is
(``tests/bench/conftest.py``): the frame's first op on both sides, on
a 64-set LLC of 8 ways that its capacity bitmasks split in two."""
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def _bench_helpers():
    """``tests/bench/bench_helpers.py`` as the module its tests import."""
    if "bench_helpers" not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            "bench_helpers", HERE / "bench" / "bench_helpers.py")
        module = importlib.util.module_from_spec(spec)
        sys.modules["bench_helpers"] = module
        spec.loader.exec_module(module)
    return sys.modules["bench_helpers"]


with open(HERE.parent / "bench" / "configs"
          / "nvdla-soc-yolov3-frame-cat.json") as f:
    _cat = json.load(f)

_helpers = _bench_helpers()
_helpers.SMALL_TRAFFIC["fig6-frame-dram-cat"] = {}
_helpers.SMALL_CONFIG["nvdla-soc-yolov3-frame-cat"] = {
    "llc": {"size_bytes": 32768, "ways": 8, "block_bytes": 64},
    "dbb_ops": _cat["dbb_ops"][:1]}
