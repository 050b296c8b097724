"""A copy of the benchmark with small cells added, for runs on the CPU.

``make_root(dir)`` copies ``BENCHMARK.json`` and the benchmark's directory
into ``dir`` and adds, as new files and new entries only, a tiny
configuration and one tiny cell per traffic mix (``tiny.<traffic>``), each
on the driver of the mix it copies; a training mix also gets a 4-chip cell
(``.4chips``), so that the data-parallel path runs on four devices.  ``run(root, cell, ...)`` runs a cell in
this process on CPU devices, past the harness's look for a chip.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_DIR = BENCH_DIR.parent

TINY_CONFIG = dict(name="smollm-tiny", hidden_size=64, intermediate_size=128,
                   num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16, vocab_size=256)
#: SmolLM-360M's published widths at four layers: what a control needs to
#: show its error at the widths it is run at, small enough for a test
WIDE_CONFIG = dict(name="smollm-4l", num_hidden_layers=4)
#: per driver: the traffic keys changed from the mix it copies
TINY_TRAFFIC = {
    "train": dict(batch_per_chip=2, seq_len=32, batches=4),
    "serve": dict(prompt_len=16, max_new_tokens=6, rate_per_s=20.0,
                  max_batch=4, check_requests=4, drain_s=20.0),
    "allreduce": dict(bytes_per_rank=4 * 2**16),
}
#: made-up peaks: a CPU has no entry in peaks.json
CPU_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "ici_bytes_per_s": 1e10}


def make_root(root: Path, config: dict = TINY_CONFIG,
              traffic: dict = TINY_TRAFFIC) -> dict[str, str]:
    """Build the copy, with ``config`` (changes to SmolLM-360M's file, and
    its ``name``) in place of the full model and ``traffic`` (changes by
    driver) to each mix; returns {small cell name: the cell it copies}."""
    shutil.copy(REPO_DIR / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(BENCH_DIR, root / BENCH_DIR.name,
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    bdir = root / BENCH_DIR.name
    bench = json.loads((root / "BENCHMARK.json").read_text())

    cfg = json.loads((bdir / "configs" / "smollm-360m.json").read_text())
    cfg.update(config)
    small = config["name"]
    (bdir / "configs" / f"{small}.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": small, "source": cfg["source"],
                             "file": f"{BENCH_DIR.name}/configs/{small}.json",
                             "reduced": [], "why": "CPU rehearsal"})
    made = {}
    for w in list(bench["workloads"]):
        mix = json.loads((bdir / "traffic" / f"{w['traffic']}.json").read_text())
        mix.update(traffic[mix["driver"]])
        name = f"tiny.{w['traffic']}"
        (bdir / "traffic" / f"{name}.json").write_text(json.dumps(mix))
        config = small if w["config"] == "smollm-360m" else w["config"]
        cells = {f"{config}.{name}": w["chips"]}
        if mix["driver"] == "train":         # the same mix, data-parallel
            cells[f"{config}.{name}.4chips"] = 4
        for cell, chips in cells.items():
            bench["workloads"].append(dict(w, name=cell, config=config, traffic=name,
                                           chips=chips))
            for m in bench["end_to_end"] + bench["per_layer"]:
                if w["name"] in m.get("workloads", ()):
                    m["workloads"].append(cell)
            made[cell] = w["name"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return made


def _paths() -> None:
    for p in (str(REPO_DIR), str(REPO_DIR / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def run(root: Path, cell_name: str, *, seed: int, seconds: float,
        trace: bool = False, devices=None) -> dict:
    """Run one cell of the copy at ``root`` in this process."""
    import jax

    _paths()
    from chipbench import bench

    cell = bench.load_cell(cell_name, root)
    devices = devices or jax.devices()[:cell.chips]
    return bench.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                          devices=devices, peaks=CPU_PEAKS,
                          started=time.perf_counter(),
                          out_dir=root / "out" / cell_name,
                          log=lambda m: print(m, file=sys.stderr))


# ---------------------------------------------------------------------------
# faults planted in the program's timed path
# ---------------------------------------------------------------------------

def _patch_step(wrap):
    import repro.training as training

    orig = training.make_train_step

    def make(*args, **kwargs):
        return wrap(orig(*args, **kwargs))
    training.make_train_step = make


def _state_unchanged():
    def wrap(step):
        def broken(state, batch):
            return state, step(state, batch)[1]
        return broken
    _patch_step(wrap)


def _half_batch():
    def wrap(step):
        def broken(state, batch):
            import jax
            return step(state, jax.tree_util.tree_map(
                lambda x: x[: x.shape[0] // 2], batch))
        return broken
    _patch_step(wrap)


def _no_exchange():
    import repro.training.train_step as ts

    ts.sync_gradients = lambda grads, axis, **kw: grads


def _allreduce(wrap):
    import repro.core.collectives as coll

    orig = coll.all_reduce
    coll.all_reduce = lambda x, axis, **kw: wrap(x, orig(x, axis, **kw))


def _decode(wrap):
    import repro.serving.engine as engine

    orig = engine.make_decode_fn

    def make(cfg):
        decode = orig(cfg)

        def broken(params, tokens, caches):
            nxt, caches = decode(params, tokens, caches)
            return wrap(nxt, cfg), caches
        return broken
    engine.make_decode_fn = make


def _half_rows(nxt, cfg):
    half = nxt.shape[0] // 2
    return nxt.at[half:2 * half].set(nxt[:half]) if half else (nxt + 1) % cfg.vocab_size


FAULTS = {
    "train": {
        "state_unchanged": _state_unchanged,
        "half_batch": _half_batch,
        "no_exchange": _no_exchange,
    },
    "allreduce": {
        "no_exchange": lambda: _allreduce(lambda x, out: x),
        "half_unreduced": lambda: _allreduce(
            lambda x, out: out.at[: x.shape[0] // 2].set(x[: x.shape[0] // 2])),
        "altered_answer": lambda: _allreduce(lambda x, out: out.at[0].add(1.0)),
    },
    "serve": {
        "altered_token": lambda: _decode(
            lambda nxt, cfg: nxt.at[0].set((nxt[0] + 1) % cfg.vocab_size)),
        "half_batch": lambda: _decode(_half_rows),
    },
}


def main() -> None:
    """``python -m chipbench.tests.tiny <root> <cell> <seed> <seconds> [fault]``:
    one run of a tiny cell, its result line last on standard output."""
    _paths()
    root, cell, seed, seconds = sys.argv[1], sys.argv[2], int(sys.argv[3]), float(sys.argv[4])
    if len(sys.argv) > 5:
        from chipbench import bench
        driver = bench.load_cell(cell, Path(root)).traffic["driver"]
        FAULTS[driver][sys.argv[5]]()
    result = run(Path(root), cell, seed=seed, seconds=seconds)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
