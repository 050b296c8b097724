"""The program's host spans as ``chipbench/spans.py`` reads them.

``small_profile`` is written out by hand: one serving batch of two decode
steps inside the harness's spans, with the engine's spans nested as
``ServingEngine.run_batch`` nests them, and device operations between.
Self times, counts, argument sums and named idle gaps are worked out below
by hand.  ``data/serve_spans.pbtxt`` is a piece of a traced run of
``smollm-360m.serve.poisson`` on a TPU v5e, cut by
``tools/record_spans_fixture.py``.  ``data/dp1_3ms.summary.json`` is what
``trace.py`` made of ``data/dp1_3ms.pbtxt`` when the program spans were
added: the harness's own reduction stays as it was.
"""

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import spans, trace

DATA = Path(__file__).parent / "data"
METRICS = Path(__file__).resolve().parents[1] / "metrics"
NS = 1000  # picoseconds per nanosecond in the proto


def _plane(pid, name, lines):
    """A plane of the text proto; events are (name, start, duration, args)."""
    names = sorted({e[0] for _, evs in lines for e in evs})
    keys = sorted({k for _, evs in lines for e in evs for k in e[3]})
    body = ""
    for lid, (lname, events) in enumerate(lines, 1):
        evs = ""
        for n, s, d, args in events:
            stats = "".join(f" stats {{ metadata_id: {keys.index(k) + 1} int64_value: {v} }}"
                            for k, v in args.items())
            evs += (f"    events {{ metadata_id: {names.index(n) + 1} offset_ps: {s * NS} "
                    f"duration_ps: {d * NS}{stats} }}\n")
        body += f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n{evs}  }}\n'
    meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                   for i, n in enumerate(names, 1))
    meta += "".join(f'  stat_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}\n'
                    for i, k in enumerate(keys, 1))
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


def small_profile(program_spans=True):
    from jax.profiler import ProfileData

    # times in ns; the window is [100, 1100)
    host = [("chipbench.window", 100, 1000, {}),
            ("chipbench.batch", 100, 800, {}),                      # [100, 900)
            ("chipbench.wait", 900, 200, {})]                       # [900, 1100)
    ours = [("r2ccl.serve.batch", 110, 780, {"batch": 1, "size": 3}),  # [110, 890)
            ("r2ccl.serve.alloc", 120, 80, {"batch": 1}),          # [120, 200)
            ("r2ccl.serve.prefill", 200, 100, {"batch": 1}),       # [200, 300)
            ("r2ccl.serve.decode", 300, 200, {"batch": 1, "step": 0}),
            ("r2ccl.serve.dispatch", 310, 40, {}),                 # [310, 350)
            ("r2ccl.serve.block", 350, 100, {}),                   # [350, 450)
            ("r2ccl.serve.readback", 450, 40, {"reads": 3}),       # [450, 490)
            ("r2ccl.serve.decode", 500, 200, {"batch": 1, "step": 1}),
            ("r2ccl.serve.dispatch", 510, 20, {}),                 # [510, 530)
            ("r2ccl.serve.block", 530, 120, {}),                   # [530, 650)
            ("r2ccl.serve.readback", 650, 40, {"reads": 2}),       # [650, 690)
            ("r2ccl.serve.batch", 1200, 50, {"batch": 2})]         # after the window
    ops = [("%fusion.1 = f32[8] fusion()", 210, 80, {}),           # prefill [210, 290)
           ("%fusion.2 = f32[8] fusion()", 340, 100, {}),          # decode [340, 440)
           ("%fusion.2 = f32[8] fusion()", 525, 115, {})]          # decode [525, 640)
    host = host + ours if program_spans else host
    txt = (_plane(1, "/host:CPU", [("python", host)])
           + _plane(2, "/device:TPU:0", [("XLA Ops", ops)]))
    return ProfileData.from_text_proto(txt)


@pytest.fixture(scope="module")
def small():
    return spans.reduce_profile(small_profile(), gaps=True)


def test_self_time_count_and_arguments(small):
    ns = lambda x: x * 1e-9
    assert small.window_s == pytest.approx(ns(1000))
    assert small.self_s == pytest.approx({
        # 780 less alloc 80, prefill 100 and two decode steps of 200
        "r2ccl.serve.batch": ns(200),
        "r2ccl.serve.alloc": ns(80), "r2ccl.serve.prefill": ns(100),
        # each step's 200 less its three children
        "r2ccl.serve.decode": ns(20 + 20),
        "r2ccl.serve.dispatch": ns(40 + 20), "r2ccl.serve.block": ns(100 + 120),
        "r2ccl.serve.readback": ns(40 + 40)})
    assert small.count == {"r2ccl.serve.batch": 1, "r2ccl.serve.alloc": 1,
                           "r2ccl.serve.prefill": 1, "r2ccl.serve.decode": 2,
                           "r2ccl.serve.dispatch": 2, "r2ccl.serve.block": 2,
                           "r2ccl.serve.readback": 2}
    assert small.args["r2ccl.serve.readback"] == {"reads": 5}
    assert small.args["r2ccl.serve.decode"] == {"batch": 2, "step": 1}
    assert small.args["r2ccl.serve.batch"] == {"batch": 1, "size": 3}
    # a decode step's self time and its children's add up to the step
    family = ("r2ccl.serve.decode", "r2ccl.serve.dispatch", "r2ccl.serve.block",
              "r2ccl.serve.readback")
    assert sum(small.self_s[n] for n in family) == pytest.approx(ns(400))
    assert small.per("r2ccl.serve.dispatch", "r2ccl.serve.decode") == pytest.approx(
        1e3 * ns(30))
    assert small.per("r2ccl.serve.alloc", "r2ccl.serve.alloc") == pytest.approx(1e3 * ns(80))
    assert small.per("r2ccl.serve.nothing", "r2ccl.serve.decode") is None


def test_gaps_named_by_innermost_span(small):
    # chip 0 idles [100, 210), [290, 340), [440, 525), [640, 1100), each cut
    # at every span edge inside it
    want = [("chipbench.batch", 10), ("r2ccl.serve.batch", 10),
            ("r2ccl.serve.alloc", 80), ("r2ccl.serve.prefill", 10),
            ("r2ccl.serve.prefill", 10), ("r2ccl.serve.decode", 10),
            ("r2ccl.serve.dispatch", 30),
            ("r2ccl.serve.block", 10), ("r2ccl.serve.readback", 40),
            ("r2ccl.serve.decode", 10), ("r2ccl.serve.decode", 10),
            ("r2ccl.serve.dispatch", 15),
            ("r2ccl.serve.block", 10), ("r2ccl.serve.readback", 40),
            ("r2ccl.serve.decode", 10), ("r2ccl.serve.batch", 190),
            ("chipbench.batch", 10), ("chipbench.wait", 200)]
    assert sorted(small.gaps, key=lambda g: -g[1]) == small.gaps
    assert sorted((n, round(s * 1e9)) for n, s in small.gaps) == sorted(want)
    # the harness's reduction of the same trace is unchanged by the program spans
    base = trace.reduce_profile(small_profile(False), window_span="chipbench.window")
    both = trace.reduce_profile(small_profile(), window_span="chipbench.window")
    assert dataclasses.asdict(base) == dataclasses.asdict(both)


def test_a_program_without_spans_reads_nothing():
    found = spans.reduce_profile(small_profile(program_spans=False))
    assert found.self_s == {} and found.count == {} and found.args == {}
    assert found.per("r2ccl.serve.dispatch", "r2ccl.serve.decode") is None


def _metric(name):
    spec = importlib.util.spec_from_file_location(f"m_{name.replace('.', '_')}",
                                                  METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_metrics_read_the_spans(small, monkeypatch, tmp_path):
    run = SimpleNamespace(cell=SimpleNamespace(bench_dir=tmp_path, name="cell"))
    # no trace written: nothing to read
    assert all(_metric(m).read(run) is None
               for m in ("alloc_ms.serve", "dispatch_ms.serve", "readback_ms.serve"))
    monkeypatch.setattr(spans, "for_run", lambda run: small)
    assert _metric("alloc_ms.serve").read(run) == pytest.approx(80e-6)
    assert _metric("dispatch_ms.serve").read(run) == pytest.approx(30e-6)
    assert _metric("readback_ms.serve").read(run) == pytest.approx(40e-6)


def test_recorded_serve_piece(monkeypatch):
    """About 10 ms of a traced serve run on a TPU v5e: decode steps of a
    batch of one, each a dispatch, a block and one read."""
    from jax.profiler import ProfileData

    data = ProfileData.from_text_proto((DATA / "serve_spans.pbtxt").read_text())
    found = spans.reduce_profile(data, gaps=True)
    assert found.args["r2ccl.serve.batch"] == {"batch": 9, "size": 1, "new_tokens": 64}
    assert found.args["r2ccl.serve.readback"] == {"reads": found.count["r2ccl.serve.readback"]}
    run = SimpleNamespace(cell=None)
    monkeypatch.setattr(spans, "for_run", lambda run: found)
    dispatch = _metric("dispatch_ms.serve").read(run)
    readback = _metric("readback_ms.serve").read(run)
    assert 0 < dispatch < readback < 2.0                   # ms a decode step
    # the device idles in the block only while the host waits; every gap
    # of the piece lies inside a program span
    assert {n for n, _ in found.gaps} <= {"r2ccl.serve.block", "r2ccl.serve.readback",
                                          "r2ccl.serve.dispatch", "r2ccl.serve.decode"}
    summary = trace.reduce_profile(data, window_span="chipbench.window")
    assert sum(t for _, t in found.gaps) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-9)


def test_recorded_dp1_summary_unchanged():
    """Field by field, what trace.py makes of the recorded dp1 piece."""
    from jax.profiler import ProfileData

    got = dataclasses.asdict(trace.reduce_profile(
        ProfileData.from_text_proto((DATA / "dp1_3ms.pbtxt").read_text()),
        window_span="chipbench.window"))
    want = json.loads((DATA / "dp1_3ms.summary.json").read_text())
    assert sorted(got) == sorted(want)
    for field, value in want.items():
        if isinstance(value, dict):
            assert got[field] == pytest.approx(value, rel=1e-12, abs=1e-15), field
        elif isinstance(value, list):
            assert [list(g) for g in got[field]] == value, field
        else:
            assert got[field] == pytest.approx(value, rel=1e-12), field
