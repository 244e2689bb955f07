"""trace_reduce on hand-made events and on a trace recorded on a TPU v5e
(``testdata/softmax_2seg.xplane.pb``: two one-round segments of the
softmax_fmnist federation, N=50 M=10 H=5 b2=20, on the flat-kernel plan).

    python -m pytest bench/tests
"""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import trace_reduce as tr  # noqa: E402

TRACE = Path(__file__).resolve().parents[1] / "testdata" / \
    "softmax_2seg.xplane.pb"


def test_base_name():
    assert tr.base_name("%zo_walk.10 = f32[10,512,128] custom-call(...)") \
        == "zo_walk"
    assert tr.base_name("%all-reduce.3 = f32[8] all-reduce(x)") \
        == "all-reduce"
    assert tr.base_name("copy-done") == "copy-done"


def test_containers_are_not_work():
    # a while loop enclosing two kernels and a gap: only the kernels count
    ops = [("%while.1 = () while()", 0, 100),
           ("%zo_walk.2 = f32[] custom-call()", 10, 30),
           ("%fusion.3 = f32[] fusion()", 50, 90)]
    red = tr.reduce_events({"/device:TPU:0": {"ops": ops, "async": []}},
                           [("bench.window", 0, 200)])
    assert red["busy_s"] == pytest.approx(60e-9)
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["op_s"] == pytest.approx({"zo_walk": 20e-9, "fusion": 40e-9})
    assert "while" not in red["op_s"]


def test_busy_is_averaged_over_chips_and_ops_summed():
    dev = {"/device:TPU:0": {"ops": [("%zo_walk.1 = x", 0, 40)],
                             "async": []},
           "/device:TPU:1": {"ops": [("%zo_walk.1 = x", 0, 20)],
                             "async": []}}
    red = tr.reduce_events(dev, [("bench.window", 0, 100)])
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["op_s"]["zo_walk"] == pytest.approx(60e-9)


def test_exposed_collective_time():
    # all-reduce 40..80 overlaps compute 30..60: 20 ns exposed; an async
    # all-reduce 90..95 with nothing beside it is exposed whole
    ops = [("%fusion.1 = x", 30, 60), ("%all-reduce.2 = x", 40, 80)]
    asy = [("%all-reduce-start.3 = x", 90, 95)]
    red = tr.reduce_events({"/device:TPU:0": {"ops": ops, "async": asy}},
                           [("bench.window", 0, 100)])
    assert red["collective_s"] == pytest.approx(45e-9)
    assert red["collective_exposed_s"] == pytest.approx(25e-9)


def test_window_clips_and_gaps_name_the_host_span():
    ops = [("%fusion.1 = x", 0, 10), ("%fusion.2 = x", 50, 150)]
    host = [("bench.window", 5, 120), ("bench.dispatch", 5, 12),
            ("bench.wait", 12, 120)]
    red = tr.reduce_events({"/device:TPU:0": {"ops": ops, "async": []}},
                           host)
    assert red["window_s"] == pytest.approx(115e-9)
    assert red["busy_s"] == pytest.approx(75e-9)
    assert red["breakdown"]["idle_gaps"][0] == ["bench.wait",
                                               pytest.approx(40e-9)]


def test_recorded_chip_trace():
    red = tr.reduce(str(TRACE))
    devs, _ = tr.read_trace(str(TRACE))
    walks = [e for e in devs["/device:TPU:0"]["ops"]
             if tr.base_name(e[0]) == "zo_walk"]
    # H·b2 = 100 vmapped walk calls a round, two rounds
    assert len(walks) == 200
    assert red["op_s"]["zo_walk"] == pytest.approx(0.018599263, rel=1e-6)
    assert red["op_s"]["zo_dirnorms"] == pytest.approx(0.009755028, rel=1e-6)
    assert red["op_s"]["zo_replay"] == pytest.approx(0.009339546, rel=1e-6)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] == pytest.approx(0.043726426, rel=1e-6)
    assert red["collective_s"] == 0.0
    names = [n for n, _ in red["breakdown"]["device_ops"]]
    assert names[:3] == ["zo_walk", "zo_dirnorms", "zo_replay"]
    assert "while" not in red["op_s"]
