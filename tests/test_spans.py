"""The program's profiler spans and device scopes (``repro.core.spans``).

Host spans: ``ServingEngine.run_batch`` under ``jax.profiler`` writes each
``r2ccl.serve.*`` span as often as the batch asks for, with its arguments,
and nested as documented; with a fake clock its results and its clock
readings are what they were without spans.  Device scopes: the compiled
train step and the AllReduce carry the scope names in the ``op_name``
metadata of the instructions they should cover.
"""

import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spans
from repro.core.failures import Failure, FailureType
from repro.core.comm_sim import VLLM_RESTART_DELAY
from repro.models import get_smoke_config, init_model
from repro.serving import Request, ServingEngine

SERVE_SPANS = (spans.SERVE_BATCH, spans.SERVE_ALLOC, spans.SERVE_PREFILL,
               spans.SERVE_DECODE, spans.SERVE_DISPATCH, spans.SERVE_BLOCK,
               spans.SERVE_READBACK)


class FakeClock:
    """Reads 0, 1, 2, ...: every engine timing is a count of clock reads."""

    def __init__(self):
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        return float(self.reads - 1)


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("smollm-360m")
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(cfg, new=(5, 5, 5)):
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, 10), max_new_tokens=n)
            for n in new]


def _profiled(fn, trace_dir):
    """Run ``fn()`` under the profiler; return its result and the host
    spans of the trace as (name, start, end, args)."""
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(trace_dir / "plugins/profile/*/*.xplane.pb"))
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("r2ccl."):
                        found.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                      dict(e.stats)))
    return out, found


@pytest.mark.parametrize("new", [(5, 5, 5), (3, 6)])
def test_run_batch_writes_each_span(model, tmp_path, new):
    cfg, params = model
    eng = ServingEngine(cfg, params, context_len=32, strategy="r2ccl",
                        clock=FakeClock())
    eng.run_batch(_reqs(cfg, new))                  # compiles; not traced
    results, found = _profiled(lambda: eng.run_batch(_reqs(cfg, new)), tmp_path)
    by = {n: [f for f in found if f[0] == n] for n in SERVE_SPANS}
    steps = max(new) - 1
    assert {n: len(v) for n, v in by.items()} == {
        spans.SERVE_BATCH: 1, spans.SERVE_ALLOC: 1, spans.SERVE_PREFILL: 1,
        spans.SERVE_DECODE: steps, spans.SERVE_DISPATCH: steps,
        spans.SERVE_BLOCK: steps, spans.SERVE_READBACK: steps}
    (_, b0, b1, args), = by[spans.SERVE_BATCH]
    assert args == {"batch": 2, "size": len(new), "new_tokens": max(new)}
    for name in (spans.SERVE_ALLOC, spans.SERVE_PREFILL, spans.SERVE_DECODE):
        assert all(a["batch"] == 2 for _, _, _, a in by[name])
    assert [a["step"] for _, _, _, a in by[spans.SERVE_DECODE]] == list(range(steps))
    # each request is read once per token after the first, until it is done
    reads = [a["reads"] for _, _, _, a in by[spans.SERVE_READBACK]]
    assert reads == [sum(n > j + 1 for n in new) for j in range(steps)]
    assert sum(reads) == sum(len(r.tokens) - 1 for r in results)
    # ... all from one device-to-host transfer of the step's tokens
    assert [a["transfers"] for _, _, _, a in by[spans.SERVE_READBACK]] == [1] * steps
    # every span lies inside the batch; each decode step holds its three
    assert all(b0 <= s <= e <= b1 for _, s, e, _ in found)
    for (_, s, e, _), *kids in zip(by[spans.SERVE_DECODE], by[spans.SERVE_DISPATCH],
                                   by[spans.SERVE_BLOCK], by[spans.SERVE_READBACK]):
        assert all(s <= ks <= ke <= e for _, ks, ke, _ in kids)
        assert [k[0] for k in sorted(kids, key=lambda k: k[1])] == [
            spans.SERVE_DISPATCH, spans.SERVE_BLOCK, spans.SERVE_READBACK]


@pytest.mark.parametrize("strategy,fail_at", [("r2ccl", None), ("restart", 2)])
def test_spans_change_no_result_or_clock_read(model, tmp_path, strategy, fail_at):
    """The spans read no clock of their own: with a clock that counts its
    reads, every virtual time is the closed form of the same reads, with
    the profiler off and on."""
    cfg, params = model
    new = 5
    fail = Failure(FailureType.NIC_HARDWARE, 0, 0)

    def run():
        clock = FakeClock()
        eng = ServingEngine(cfg, params, context_len=32, strategy=strategy,
                            clock=clock)
        res = eng.run_batch(_reqs(cfg), fail_at_step=fail_at,
                            failure=fail if fail_at is not None else None)
        return clock.reads, res

    off_reads, off = run()
    (on_reads, on), _ = _profiled(run, tmp_path)
    # one read before and after prefill and each decode step
    assert off_reads == on_reads == 2 + 2 * (new - 1)
    # prefill and every decode step read one tick
    total = new + (0.0 if fail_at is None else VLLM_RESTART_DELAY + 1 + fail_at)
    for a, b in zip(off, on):
        assert a == b
        assert a.ttft == 1.0
        assert a.total_latency == pytest.approx(total)
        assert a.failovers == (fail_at is not None)


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scoped_instructions(hlo: str) -> list[tuple[str, str, list[str]]]:
    """(name, opcode, op_name components) of every instruction of a
    compiled program's text, fused computations included."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2), op.group(1).split("/") if op else []))
    return out


def test_segment_scope_is_the_schedule_stem():
    assert spans.segment_scope("partial_ar[3]+bridge") == "partial_ar"
    assert spans.segment_scope("ring_ar[4]") == "ring_ar"


@pytest.mark.parametrize("arch", ["smollm-360m", "deepseek-v3-671b"])
def test_train_step_carries_layer_and_optimizer_scopes(arch):
    """Attention (GQA or MLA), FFN (MLP or MoE) and the optimizer, in the
    forward, backward and rematerialised instructions alike."""
    from repro.optim import AdamWConfig
    from repro.training import init_train_state, make_train_step

    cfg = get_smoke_config(arch)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg)[0])
    state = jax.eval_shape(init_train_state, params)
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3), sync="xla"))
    hlo = step.lower(state, {"tokens": tok, "labels": tok}).compile().as_text()
    found = {c for _, _, comps in scoped_instructions(hlo) for c in comps}
    assert {spans.ATTENTION, spans.FFN, spans.OPTIMIZER} <= found
    assert spans.SYNC not in found                # sync="xla": nothing to wrap
    backward = [comps for _, _, comps in scoped_instructions(hlo)
                if any(c.startswith("transpose(") for c in comps)]
    assert any(spans.ATTENTION in c for c in backward)
    assert any(spans.FFN in c for c in backward)


_FOUR_DEVICES = """
import json
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np
from repro.core.collectives import all_reduce
from repro.core.planner import CommConfig
from repro.models import get_smoke_config, init_model
from repro.optim import AdamWConfig
from repro.training import init_train_state, make_train_step

cfg = get_smoke_config("smollm-360m")
mesh = jax.make_mesh((4, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg)[0])
state = jax.eval_shape(init_train_state, params)
tok = jax.ShapeDtypeStruct((8, 16), jnp.int32,
                           sharding=NamedSharding(mesh, P("data")))
step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3), sync="r2ccl",
                               comm=CommConfig(mode="ring"), mesh=mesh))
train = step.lower(state, {"tokens": tok, "labels": tok}).compile().as_text()

flat = Mesh(np.array(jax.devices()[:4]), ("data",),
            axis_types=(jax.sharding.AxisType.Auto,))
fn = jax.jit(jax.shard_map(
    lambda v: all_reduce(v[0], "data", mode="r2ccl", degraded=1,
                         lost_fraction=0.5, g=2)[None],
    mesh=flat, in_specs=P("data", None), out_specs=P("data", None),
    check_vma=False))
x = jax.ShapeDtypeStruct((4, 4096), jnp.float32,
                         sharding=NamedSharding(flat, P("data", None)))
ar = fn.lower(x).compile().as_text()
print(json.dumps({"train": train, "allreduce": ar}))
"""


@pytest.fixture(scope="module")
def four_device_hlo(multidevice):
    out = multidevice(_FOUR_DEVICES, devices=4)
    return json.loads(out.strip().splitlines()[-1])


def _permutes(hlo):
    return [(n, comps) for n, op, comps in scoped_instructions(hlo)
            if op.startswith("collective-permute")]


def test_ring_sync_permutes_sit_under_sync(four_device_hlo):
    permutes = _permutes(four_device_hlo["train"])
    assert permutes
    for name, comps in permutes:
        assert spans.SYNC in comps and "ring_ar" in comps, name
    found = {c for _, _, comps in scoped_instructions(four_device_hlo["train"])
             for c in comps}
    assert {spans.ATTENTION, spans.FFN, spans.OPTIMIZER, spans.MERGE} <= found


def test_allreduce_segments_merge_and_pack(four_device_hlo):
    instrs = scoped_instructions(four_device_hlo["allreduce"])
    permutes = _permutes(four_device_hlo["allreduce"])
    segments = {c for _, comps in permutes for c in comps} & {"ring_ar", "partial_ar"}
    assert segments == {"ring_ar", "partial_ar"}
    for name, comps in permutes:
        assert ("ring_ar" in comps) != ("partial_ar" in comps), name
        assert spans.MERGE not in comps and spans.PACK not in comps, name
    updates = [(n, comps) for n, op, comps in instrs if op == "dynamic-update-slice"]
    assert updates
    for name, comps in updates:
        assert spans.MERGE in comps, name
    assert any(spans.PACK in comps for _, _, comps in instrs)
