"""The trace reduction on small traces whose answers are known.

``small_profile`` is written out by hand: two chips, a window span on the
host with step spans inside it, a ``while`` event that encloses the events
of its body, and operations that run past the window's edges.  Its busy
time, its split by operation and class and its idle gaps are worked out
below by hand, not by the code under test.  ``data/dp1_3ms.pbtxt`` is 3 ms
of a recorded trace of ``smollm-360m.train.dp1`` on a TPU v5e (cut by
``tools/record_fixture.py``).
"""

from pathlib import Path

import numpy as np
import pytest

from chipbench import trace

RECORDED = Path(__file__).parent / "data" / "dp1_3ms.pbtxt"

NS = 1000  # picoseconds per nanosecond in the proto


def _plane(pid, name, lines, names):
    meta = "".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for i, n in enumerate(names, 1))
    body = ""
    for lid, (lname, events) in enumerate(lines, 1):
        evs = "".join(
            f"    events {{ metadata_id: {names.index(n) + 1} "
            f"offset_ps: {s * NS} duration_ps: {d * NS} }}\n"
            for n, s, d in events)
        body += f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n{evs}  }}\n'
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


def small_profile():
    from jax.profiler import ProfileData

    # times in ns; the window is [100, 1100)
    host = [("chipbench.window", 100, 1000),
            ("chipbench.step", 100, 400),          # [100, 500)
            ("chipbench.step", 500, 400),          # [500, 900)
            ("chipbench.wait", 900, 200)]          # [900, 1100)
    ops0 = [("%fusion.7 = f32[2] fusion()", 50, 40),  # before the window
            ("%while.1 = (s32[]) while()", 100, 350),  # [100, 450), encloses:
            ("%fusion.1 = f32[8] fusion()", 120, 80),        # [120, 200)
            ("%fusion.2 = f32[8] fusion()", 200, 50),        # [200, 250)
            ("%collective-permute-start.3 = f32[8] collective-permute-start()",
             300, 100),                                      # [300, 400)
            ("%collective-permute-done.3 = f32[8] collective-permute-done()",
             400, 40),                                       # [400, 440)
            ("%fusion.1 = f32[8] fusion()", 600, 200),     # [600, 800)
            ("%fusion.9 = f32[8] fusion()", 1050, 100)]    # clipped to 1100
    ops1 = [("%fusion.1 = f32[8] fusion()", 100, 500),     # [100, 600)
            ("%all-reduce.4 = f32[8] all-reduce()", 600, 100),
            ("%fusion.1 = f32[8] fusion()", 2000, 100)]    # outside the window
    mods0 = [("jit_step(1)", 100, 350), ("jit_step(1)", 600, 200),
             ("jit_other(2)", 2000, 10)]
    host_names = sorted({n for n, _, _ in host})
    ops0_names = sorted({n for n, _, _ in ops0} | {n for n, _, _ in mods0})
    ops1_names = sorted({n for n, _, _ in ops1})
    txt = (_plane(1, "/host:CPU", [("python", host)], host_names)
           + _plane(2, "/device:TPU:0", [("XLA Ops", ops0), ("XLA Modules", mods0)],
                    ops0_names)
           + _plane(3, "/device:TPU:1", [("XLA Ops", ops1)], ops1_names))
    return ProfileData.from_text_proto(txt)


@pytest.fixture(scope="module")
def small():
    return trace.reduce_profile(small_profile(), window_span="chipbench.window")


def test_window_and_busy_union(small):
    assert small.chips == 2
    assert small.window_s == pytest.approx(1000e-9)
    # chip 0: [100, 450) + [600, 800) + [1050, 1100); chip 1: [100, 700)
    assert small.busy_s == pytest.approx((600 + 600) / 2 * 1e-9)


def test_self_time_by_op_kind_and_class(small):
    ns = lambda x: x / 2 * 1e-9                     # averaged over 2 chips
    # the while's own time: 350 less its body's 80 + 50 + 100 + 40
    assert small.op_s["while.1"] == pytest.approx(ns(80))
    assert small.op_s["fusion.1"] == pytest.approx(ns(80 + 200 + 500))
    assert small.kind_s["fusion"] == pytest.approx(ns(80 + 200 + 50 + 50 + 500))
    assert small.class_s["collective-permute"] == pytest.approx(ns(140))
    assert small.class_s["all-reduce"] == pytest.approx(ns(100))
    assert sum(small.class_s.values()) == pytest.approx(small.busy_s)
    assert small.module_s == pytest.approx({"jit_step(1)": ns(550)})
    assert small.module_runs == {"jit_step(1)": 1.0}


def test_gaps_named_by_host_span(small):
    # chip 0 idles [450, 600), cut at the second step's start (500), then
    # [800, 1050), cut where the wait begins (900)
    assert sorted(small.gaps, key=lambda g: -g[1]) == small.gaps
    got = sorted((n, round(s * 1e9)) for n, s in small.gaps)
    assert got == sorted([("chipbench.step", 50), ("chipbench.step", 100),
                          ("chipbench.step", 100), ("chipbench.wait", 150)])
    b = small.breakdown(top=2)
    assert [n for n, _ in b["idle_gaps"]] == ["chipbench.wait", "chipbench.step"]
    assert b["device_ops"][0][0] == "fusion.1"
    assert len(b["device_ops"]) == 2


def test_recorded_trace():
    """On 3 ms of a real trace: the busy time agrees with a union computed
    here on a 1 ns grid, and self times add up to the busy time (events on
    a line nest and top-level events do not overlap)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_text_proto(RECORDED.read_text())
    got = trace.reduce_profile(data, window_span="chipbench.window")
    host = next(p for p in data.planes if p.name.startswith("/host:"))
    (_, w0, wd), = [e for line in host.lines for e in trace._events(line)
                    if e[0] == "chipbench.window"]
    dev = next(p for p in data.planes if p.name == "/device:TPU:0")
    ops = next(line for line in dev.lines if line.name == "XLA Ops")
    grid = np.zeros(int(round(wd)), bool)
    for _, s, d in trace._events(ops):
        a, b = int(round(max(s, w0) - w0)), int(round(min(s + d, w0 + wd) - w0))
        grid[max(a, 0):max(b, 0)] = True
    assert got.busy_s == pytest.approx(grid.sum() * 1e-9, rel=1e-4)
    assert got.window_s == pytest.approx(3e-3)
    assert sum(got.op_s.values()) == pytest.approx(got.busy_s, rel=1e-6)
    assert all(v >= 0 for v in got.op_s.values())
    assert "while" in got.kind_s and "fusion" in got.kind_s


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError, match="no host span"):
        trace.reduce_profile(small_profile(), window_span="chipbench.nothing")


def test_op_names():
    assert trace.op_name("%fusion.12 = bf16[8]{0} fusion(%p), kind=kLoop") == "fusion.12"
    assert trace.op_kind("%fusion.12 = bf16[8]{0} fusion(%p)") == "fusion"
    assert trace.op_kind("fusion.12") == "fusion"
    assert trace.op_kind("collective-permute-done.3.1") == "collective-permute-done"
    assert trace.op_class("collective-permute-start.2") == "collective-permute"
    assert trace.op_class("copy.7") == "other"
