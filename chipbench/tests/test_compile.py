"""Every cell's programs compile for a described TPU v5e 2x2 and fit a chip.

The programs are those ``tools/compile_cells.py`` compiles: the window's
(train step, AllReduce, prefill and decode at the largest batch) and the
reference's, at the cells' own sizes.  Nothing runs.  The topology is
described only inside the module fixture: one process at a time may load
the TPU library.
"""

import importlib.util
from pathlib import Path

import pytest

from chipbench.tests import tiny

V5E_HBM_GIB = 16e9 / 2**30
CELLS = ["smollm-360m.train.dp1", "allreduce-2x2.degraded-64MiB",
         "smollm-360m.serve.poisson"]


@pytest.fixture(scope="module")
def compile_cells():
    tiny._paths()
    path = Path(__file__).resolve().parents[1] / "tools" / "compile_cells.py"
    spec = importlib.util.spec_from_file_location("compile_cells", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

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


@pytest.mark.parametrize("name", CELLS)
def test_cell_programs_compile_and_fit(compile_cells, topo, name):
    from chipbench import bench

    cell = bench.load_cell(name)
    compile_fn = {"train": compile_cells.compile_train,
                  "allreduce": compile_cells.compile_allreduce,
                  "serve": compile_cells.compile_serve}[cell.traffic["driver"]]
    rows = compile_fn(cell, topo.devices[:cell.chips])
    assert rows
    for row in rows:
        assert row["total_GiB"] < V5E_HBM_GIB, row
    if cell.traffic["driver"] == "allreduce":
        assert rows[0]["collective_permutes"] > 0
