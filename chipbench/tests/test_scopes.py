"""Instructions mapped to the program's scopes, as ``chipbench/scopes.py``
maps them: on a piece of compiled text written out here, and on the dp1
step and the AllReduce call compiled for a described TPU v5e 2x2 at the
cells' own sizes (nothing runs; each test prints its unscoped share of
instructions).  The topology is described only inside the module fixture:
one process at a time may load the TPU library.
"""

import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import scopes
from chipbench.tests import tiny

METRICS = Path(__file__).resolve().parents[1] / "metrics"

HLO = """\
HloModule jit_train_step, entry_computation_layout={()->f32[8]}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %convert.2 = f32[8]{0} convert(f32[8]{0} %param_0), metadata={op_name="jit(train_step)/pack/convert_element_type"}
  ROOT %tanh.1 = f32[8]{0} tanh(f32[8]{0} %convert.2), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/attention/tanh" source_file="x.py" source_line=3}
}

%wide.body (wide.param: (u32[], f32[8])) -> (u32[], f32[8]) {
  %wide.param = (u32[], f32[8]{0}) parameter(0)
  %dynamic-update-slice.9 = f32[8]{0} dynamic-update-slice(f32[8]{0} %x, f32[1]{0} %y, u32[] %i)
  ROOT %tuple.1 = (u32[], f32[8]{0}) tuple(u32[] %i, f32[8]{0} %dynamic-update-slice.9)
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %while.7 = (u32[], f32[8]{0}) while((u32[], f32[8]{0}) %tuple.0), condition=%wide.cond, body=%wide.body
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/attention/tanh"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/ffn/dot_general"}
  %collective-permute-start.3 = (f32[8]{0}, f32[8]{0}) collective-permute-start(f32[8]{0} %fusion.2), source_target_pairs={{0,1}}, metadata={op_name="jit(train_step)/sync/partial_ar/ppermute"}
  %dynamic-update-slice.4 = f32[8]{0} dynamic-update-slice(f32[8]{0} %p, f32[8]{0} %p, s32[] %c), metadata={op_name="jit(train_step)/sync/partial_ar/merge/dynamic_update_slice"}
  %copy.5 = f32[8]{0} copy(f32[8]{0} %p)
  ROOT %add.6 = f32[8]{0} add(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(train_step)/optimizer/add"}
}
"""


def test_scope_chains_of_a_compiled_text():
    chains = scopes.scope_chains(HLO)
    assert chains["fusion.1"] == ("attention",)
    assert chains["fusion.2"] == ("ffn",)
    assert chains["tanh.1"] == ("attention",)
    assert chains["collective-permute-start.3"] == ("sync", "partial_ar")
    assert chains["dynamic-update-slice.4"] == ("sync", "partial_ar", "merge")
    assert chains["copy.5"] == () and chains["p"] == ()
    assert chains["add.6"] == ("optimizer",)
    assert chains["dynamic-update-slice.9"] == () and chains["while.7"] == ()


def test_compiler_made_loops():
    assert scopes.compiler_loops(HLO) == {"while.7", "wide.param",
                                          "dynamic-update-slice.9", "tuple.1"}


def test_time_under_and_innermost():
    chains = scopes.scope_chains(HLO)
    op_s = {"fusion.1": 4.0, "fusion.2": 3.0, "collective-permute-start.3": 2.0,
            "dynamic-update-slice.4": 1.0, "copy.5": 0.5, "add.6": 0.25,
            "not-in-text.7": 0.125}
    assert scopes.time_under(op_s, chains, "sync") == 3.0
    assert scopes.time_under(op_s, chains, "partial_ar") == 3.0
    assert scopes.time_under(op_s, chains, "merge") == 1.0
    split = scopes.innermost(op_s, chains)
    assert split == {"attention": 4.0, "ffn": 3.0, "partial_ar": 2.0, "merge": 1.0,
                     "optimizer": 0.25, scopes.UNSCOPED: 0.625}
    assert sum(split.values()) == sum(op_s.values())


@pytest.mark.parametrize("metric,scope,unit,want", [
    ("attention_ms.train", "attention", "steps", 2e3),
    ("ffn_ms.train", "ffn", "steps", 1.5e3),
    ("sync_ms.train", "sync", "steps", 1.5e3),
    ("optimizer_ms.train", "optimizer", "steps", 0.125e3),
    # only inside a fusion, which carries its root's op_name
    ("pack_ms.allreduce", "pack", "calls", 0.0),
    ("partial_ms.allreduce", "partial_ar", "calls", 1.5e3),
])
def test_metrics_per_step_or_call(monkeypatch, metric, scope, unit, want):
    spec = importlib.util.spec_from_file_location("m", METRICS / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    op_s = {"fusion.1": 4.0, "fusion.2": 3.0, "collective-permute-start.3": 2.0,
            "dynamic-update-slice.4": 1.0, "add.6": 0.25}
    run = SimpleNamespace(window=SimpleNamespace(counts={unit: 2}),
                          trace=SimpleNamespace(op_s=op_s))
    monkeypatch.setattr(scopes, "chains_for", lambda run: scopes.scope_chains(HLO))
    got = mod.read(run)
    assert got == pytest.approx(want)
    # a program without the scopes (its op_names name none) reads nothing
    monkeypatch.setattr(scopes, "chains_for", lambda run: {})
    assert mod.read(run) is None


def test_program_text_is_compiled_past_a_warm_cache(monkeypatch, tmp_path):
    """The persistent cache's key leaves the metadata out: filled by the
    program without its scopes, it hands back that program's op_names."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    def step(scoped):
        def f(x):
            if scoped:
                with jax.named_scope("attention"):
                    return jnp.tanh(x) * 2
            return jnp.tanh(x) * 2
        return jax.jit(f)

    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (True, str(tmp_path), 0.0, 0)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    try:
        step(False).lower(x).compile()
        scoped = lambda cell, devices: step(True).lower(x).compile()
        assert "attention" not in scoped(None, None).as_text()
        monkeypatch.setitem(scopes.COMPILERS, "fake", scoped)
        text = scopes.program_text(SimpleNamespace(traffic={"driver": "fake"}), None)
        assert ("attention",) in scopes.scope_chains(text).values()
        assert jax.config.jax_enable_compilation_cache is True
    finally:
        for n, v in was.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    tiny._paths()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled(topo, name):
    from chipbench import bench

    cell = bench.load_cell(name)
    return scopes.program_text(cell, topo.devices[:cell.chips])


def _report(name, hlo):
    chains = scopes.scope_chains(hlo)
    share = sum(1 for c in chains.values() if not c) / len(chains)
    print(f"{name}: {len(chains)} instructions, {100 * share:.1f}% unscoped")
    return chains


def test_dp1_step_scopes_on_v5e(topo):
    hlo = _compiled(topo, "smollm-360m.train.dp1")
    chains = _report("dp1 step", hlo)
    found = {s for c in chains.values() for s in c}
    assert {"attention", "ffn", "sync", "optimizer"} <= found


def test_allreduce_scopes_on_v5e(topo):
    hlo = _compiled(topo, "allreduce-2x2.degraded-64MiB")
    chains = _report("allreduce call", hlo)
    permutes = [n for n in chains if n.startswith("collective-permute")]
    assert {s for n in permutes for s in chains[n]} == {"ring_ar", "partial_ar"}
    for name in permutes:
        assert ("ring_ar" in chains[name]) != ("partial_ar" in chains[name]), name
    assert any("pack" in c for c in chains.values())
    # Every update the program writes is a round's merge.  The rest sit in
    # loops that the TPU compiler makes, with no op_name, to lay out pack's
    # reshapes between the flat payload and its chunks: they stay unscoped.
    made = scopes.compiler_loops(hlo)
    updates = [n for n in chains if n.startswith("dynamic-update-slice")]
    assert updates
    unscoped = [n for n in updates if "merge" not in chains[n]]
    assert set(unscoped) <= made
    print(f"allreduce call: {len(updates) - len(unscoped)} updates under merge, "
          f"{len(unscoped)} in compiler-made loops")
