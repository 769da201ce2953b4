"""Test-size stand-ins for the cell whose traffic and configuration
files came after ``bench_helpers``'s tables: the helpers look each one
up by name, so every cell of ``BENCHMARK.json`` has one.

The whole-frame cell is cut to the frame's first op on both sides: its
configuration keeps the first row of the op table the reference reads,
and every test here builds the program's whole frame from that op only
(``frame_of_one_op``)."""
import functools

import bench_helpers
import pytest

FRAME_OPS = 1
_frame = bench_helpers.load(bench_helpers.ROOT / "bench" / "configs"
                            / "nvdla-soc-yolov3-frame.json")

bench_helpers.SMALL_TRAFFIC["fig6-frame-dram"] = {}
bench_helpers.SMALL_CONFIG.update({
    # 64 sets of 8 ways: a block a chunk boundary splits outlasts the
    # four co-runner chunks in between, so whole runs compact.  The
    # frame's first op: three segments, each ending in a short chunk,
    # beside co-runners that wrap every 256 chunks
    "nvdla-soc-yolov3-frame": {
        "llc": {"size_bytes": 32768, "ways": 8, "block_bytes": 64},
        "dbb_ops": _frame["dbb_ops"][:FRAME_OPS]},
})


@pytest.fixture(autouse=True)
def frame_of_one_op(monkeypatch):
    """Campaigns over the whole frame replay its first op only."""
    from repro.campaign import spec
    from repro.core import traces

    full = spec._model_trace

    @functools.lru_cache(maxsize=8)
    def cut(window_bursts, chunk_bursts, layer_index, regions):
        if window_bursts is None:
            return traces.network_trace(max_ops=FRAME_OPS,
                                        regions=regions or traces.REGIONS)
        return full(window_bursts, chunk_bursts, layer_index, regions)

    monkeypatch.setattr(spec, "_model_trace", cut)
