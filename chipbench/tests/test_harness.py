"""CPU rehearsal of the harness: every driver at a small size, the planted
faults that must make ``correct`` false, and a cell added by new files only.

Each run goes through ``bench.run_cell``, the whole of a run but the look for
a chip, on a copy of the benchmark (``tiny.make_root``) that adds small
cells with the real cells' traffic, drivers and limits.  Runs on several
devices go to a child process that asks XLA for four CPU devices.
"""

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench.tests import tiny

SEED = 2**32 + 2**31 + 7          # larger than 32 bits hold
FAULT_CASES = [(cell, fault)
               for cell, driver in (("smollm-tiny.tiny.train.dp1", "train"),
                                    ("smollm-tiny.tiny.train.dp1.4chips", "train"),
                                    ("allreduce-2x2.tiny.allreduce.degraded-64MiB", "allreduce"),
                                    ("smollm-tiny.tiny.serve.poisson", "serve"))
               for fault in tiny.FAULTS[driver]
               if not (fault == "no_exchange" and cell.endswith("dp1"))]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    tiny.make_root(root)
    return root


def run_child(root: Path, cell: str, seed: int, seconds: float,
              fault: str | None = None) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    args = [sys.executable, "-m", "chipbench.tests.tiny", str(root), cell,
            str(seed), str(seconds)] + ([fault] if fault else [])
    out = subprocess.run(args, cwd=tiny.REPO_DIR, env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["smollm-tiny.tiny.train.dp1",
                                  "smollm-tiny.tiny.train.dp1.4chips",
                                  "allreduce-2x2.tiny.allreduce.degraded-64MiB",
                                  "smollm-tiny.tiny.serve.poisson"])
def test_each_driver_runs_and_is_correct(root, cell):
    r = run_child(root, cell, SEED, 2.0)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(r["metrics"]) == want
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["count"] == (4 if "4chips" in cell or "allreduce" in cell else 1)


@pytest.mark.parametrize("cell,fault", FAULT_CASES)
def test_planted_fault_is_not_correct(root, cell, fault):
    r = run_child(root, cell, SEED, 1.0, fault)
    assert r["correct"] is False, (fault, r["checks"])


def _digests(root: Path) -> dict[str, str]:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and p.name != "BENCHMARK.json"}


def test_new_cell_and_metric_are_new_files_only(root, monkeypatch):
    """A throwaway cell with a per-layer metric of its own: new files and
    new entries in BENCHMARK.json, and no other file changed."""
    from chipbench import trace
    from chipbench.tests.test_trace import small_profile

    before = _digests(root)
    bdir = root / "chipbench"
    traffic = json.loads((bdir / "traffic" / "tiny.train.dp1.json").read_text())
    traffic["batch_per_chip"] = 1
    (bdir / "traffic" / "throwaway.json").write_text(json.dumps(traffic))
    (bdir / "metrics" / "steps_seen.throwaway.py").write_text(
        "def read(run):\n    return float(run.window.counts['steps'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "smollm-tiny.throwaway", "config": "smollm-tiny",
                               "traffic": "throwaway", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("smollm-tiny.throwaway")
    bench["per_layer"].append({"name": "steps_seen.throwaway", "unit": "steps",
                               "better": "higher", "source": "program_counter",
                               "layer": "training step", "moves": "train_tokens_per_s",
                               "workloads": ["smollm-tiny.throwaway"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before

    r = tiny.run(root, "smollm-tiny.throwaway", seed=SEED, seconds=1.0)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}

    # A traced run reads the cell's per-layer metrics; on the CPU there is
    # no device plane, so the reduction reads the small trace instead.
    monkeypatch.setattr(trace, "find_xplane", lambda d: "small")
    monkeypatch.setattr(trace, "reduce", lambda path, window_span: trace.reduce_profile(
        small_profile(), window_span=window_span))
    r = tiny.run(root, "smollm-tiny.throwaway", seed=SEED, seconds=1.0, trace=True)
    assert set(r["metrics"]) == {"steps_seen.throwaway"}
    assert r["metrics"]["steps_seen.throwaway"]["value"] >= 1
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
