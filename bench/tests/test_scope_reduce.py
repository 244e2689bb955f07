"""scope_reduce on hand-made events and on traces recorded on a TPU v5e:
``testdata/aircomp_2seg.xplane.pb`` (two one-round segments of the
softmax_fmnist.aircomp_fading cell, recorded by ``bench/record_trace.py``
from a program with named scopes) and ``testdata/softmax_2seg.xplane.pb``
(the same federation from a program without them).

    python -m pytest bench/tests
"""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import scope_reduce as sr  # noqa: E402
import trace_reduce as tr  # noqa: E402

DATA = Path(__file__).resolve().parents[1] / "testdata"
AIRCOMP = DATA / "aircomp_2seg.xplane.pb"
PLAIN = DATA / "softmax_2seg.xplane.pb"
SCOPES = ("fedzo.cohort", "fedzo.local", "fedzo.query", "fedzo.aggregate",
          "fedzo.eval")
TOP = ("fedzo.cohort", "fedzo.local", "fedzo.aggregate", "fedzo.eval",
       sr.UNSCOPED)


def test_scopes_on_op_name_paths():
    assert sr.scopes_on("jit(fn)/while/body/closed_call/vmap(fedzo.local)/"
                        "fedzo.query/dot_general:", SCOPES) == \
        ["fedzo.local", "fedzo.query"]
    assert sr.scopes_on("jit(fn)/fedzo.aggregate/jit(zo_walk)/pallas_call",
                        SCOPES) == ["fedzo.aggregate"]
    assert sr.scopes_on("jit(fn)/while", SCOPES) == []
    assert sr.scopes_on("fedzo.other/fedzo.localx/add:", SCOPES) == []
    assert sr.scopes_on("", SCOPES) == []


def test_scope_seconds_inclusive_and_exact_names():
    # fusion.14 and fusion.147 are different instructions; a while
    # container is not work; an op with no path is unscoped
    ops = [("%while.1 = () while()", 0, 100),
           ("%fusion.14 = f32[] fusion()", 10, 20),
           ("%fusion.147 = f32[] fusion()", 20, 50),
           ("%zo_walk.2 = f32[] custom-call()", 50, 90),
           ("%copy.9 = f32[] copy()", 90, 95)]
    paths = {"%fusion.14 = f32[] fusion()": "a/vmap(fedzo.local)/"
             "fedzo.query/dot:",
             "%fusion.147 = f32[] fusion()": "a/fedzo.cohort/slice:",
             "%zo_walk.2 = f32[] custom-call()": "a/vmap(fedzo.local)/zo:",
             "%while.1 = () while()": "a/fedzo.local/while:"}
    dev = {"/device:TPU:0": {"ops": ops, "async": []},
           "/device:TPU:1": {"ops": ops, "async": []}}
    plane_paths = {p: paths for p in dev}
    host = [("bench.window", 0, 100)]
    got = sr.scope_seconds(dev, host, plane_paths, SCOPES)
    assert got == pytest.approx(
        {"fedzo.local": 100e-9, "fedzo.query": 20e-9, "fedzo.cohort": 60e-9,
         "fedzo.aggregate": 0.0, "fedzo.eval": 0.0, "unscoped": 10e-9})
    # the ops outside the window do not count; a plane without paths is
    # all unscoped
    got = sr.scope_seconds(dev, [("bench.window", 15, 100)],
                           {"/device:TPU:0": paths}, SCOPES)
    assert got["fedzo.query"] == pytest.approx(5e-9)
    # chip 1: 5 + 30 + 40 + 5 ns with no path; chip 0: the copy's 5
    assert got["unscoped"] == pytest.approx(85e-9)


def test_recorded_aircomp_trace_partition():
    """The scopes split a chip trace of the AirComp cell: the top-level
    layers and the unscoped ops add up to the busy time, the loss queries
    sit inside the local phase, and the noise walk counts as aggregation."""
    paths = sr.op_paths(str(AIRCOMP))
    assert list(paths) == ["/device:TPU:0"]
    walks = [p for n, p in paths["/device:TPU:0"].items()
             if tr.base_name(n) == "zo_walk"]
    assert any("fedzo.aggregate" in sr.scopes_on(p, SCOPES) for p in walks)
    assert any("fedzo.local" in sr.scopes_on(p, SCOPES) for p in walks)
    devices, host = tr.read_trace(str(AIRCOMP))
    got = sr.scope_seconds(devices, host, paths, SCOPES)
    red = tr.reduce(str(AIRCOMP))
    busy_pct = 100 * red["busy_s"] / red["window_s"]
    top_pct = 100 * sum(got[s] for s in TOP) / red["window_s"]
    assert top_pct == pytest.approx(busy_pct, abs=0.1)
    assert all(got[s] > 0 for s in SCOPES)
    assert got["fedzo.query"] <= got["fedzo.local"]
    assert got["fedzo.local"] > 0.5 * red["busy_s"]


def test_scope_share_reads_the_trace_or_nothing(tmp_path):
    red = tr.reduce(str(AIRCOMP))
    ctx = {"trace_path": str(AIRCOMP), "window_s": red["window_s"],
           "chips": 1, "trace": red}
    shares = {s: sr.scope_share(ctx, s) for s in TOP}
    assert sum(shares.values()) == pytest.approx(
        100 * red["busy_s"] / red["window_s"], abs=0.1)
    assert sr.scope_share(ctx, "fedzo.query") <= shares["fedzo.local"]
    # a program that compiled no scopes, no trace, or a file that is no
    # trace: nothing, and no error
    plain = tr.reduce(str(PLAIN))
    assert sr.scope_share({**ctx, "trace_path": str(PLAIN),
                           "window_s": plain["window_s"]}, "unscoped") is None
    assert sr.latest_trace(tmp_path) is None
    bad = tmp_path / "x.xplane.pb"
    bad.write_bytes(b"\x0a\xff\xff\xff")
    assert sr.scope_share({**ctx, "trace_path": str(bad)},
                          "fedzo.local") is None
