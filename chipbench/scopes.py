"""Device time by the program's scopes in a traced run.

The program names parts of what it compiles with ``jax.named_scope``
(``src/repro/core/spans.py``): every instruction of the compiled program
carries, in the ``op_name`` of its metadata, the scopes it was traced under,
outermost first (``jit(train_step)/.../checkpoint/attention/dot_general``).
This maps each instruction to the scopes of ``SCOPES`` in its ``op_name``,
read from the compiled program's text, and sums the trace's device self
time by instruction (``trace.Summary.op_s``) over the instructions under a
scope.  In the traced window of the train and AllReduce cells only the
cell's one program runs, so the trace names its instructions.

The text is that of the cell's program compiled again as its driver
compiles it, with the same builder, shapes and shardings, and with the
persistent compilation cache off: the cache's key leaves the metadata out,
so a warm cache hands back the executable, and the ``op_name``s, of
whichever version of the program filled it (on the chip, a cache filled
before the scopes existed left every instruction unscoped).  The
instruction names do not depend on the metadata: the program's text with
and without its scopes is the same once the metadata is stripped, so the
fresh compile names the instructions of the executable that ran.
Instructions whose ``op_name`` holds none of the scopes are "unscoped".
"""

from __future__ import annotations

import functools
import re
from pathlib import Path

#: the scopes read, as the program names them
SCOPES = ("attention", "ffn", "sync", "optimizer", "pack", "merge",
          "ring_ar", "partial_ar")
UNSCOPED = "unscoped"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_chains(hlo: str) -> dict[str, tuple[str, ...]]:
    """Instruction name -> the scopes of ``SCOPES`` in its ``op_name``,
    outermost first, for every instruction of a compiled program's text."""
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            parts = op.group(1).split("/") if op else ()
            out[m.group(1)] = tuple(p for p in parts if p in SCOPES)
    return out


def compiler_loops(hlo: str) -> set[str]:
    """Instructions of the ``while`` loops that carry no ``op_name``, the
    loops included: loops the compiler made itself, as the TPU compiler
    does to change the layout of a reshape, which no scope can name."""
    comp, members, made = None, {}, set()
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        members.setdefault(comp, []).append(m.group(1))
        if " while(" in line and "op_name=" not in line:
            made.add(m.group(1))
            made.update(f"@{c}" for c in re.findall(r"(?:body|condition)=%([\w.\-]+)", line))
    return {i for c, names in members.items() if f"@{c}" in made for i in names} | {
        i for i in made if not i.startswith("@")}


def time_under(op_s: dict[str, float], chains: dict[str, tuple[str, ...]],
               scope: str) -> float:
    """Self time of the instructions under ``scope`` at any depth."""
    return sum(t for op, t in op_s.items() if scope in chains.get(op, ()))


def innermost(op_s: dict[str, float],
              chains: dict[str, tuple[str, ...]]) -> dict[str, float]:
    """Self time by each instruction's innermost scope, ``UNSCOPED`` for
    the rest: the parts add up to the sum of ``op_s``."""
    out: dict[str, float] = {}
    for op, t in op_s.items():
        chain = chains.get(op, ())
        key = chain[-1] if chain else UNSCOPED
        out[key] = out.get(key, 0.0) + t
    return out


def compile_train(cell, devices):
    """The train driver's step, compiled for ``devices`` from shapes."""
    import jax
    import jax.numpy as jnp
    from repro.training import init_train_state
    from chipbench import reference
    from chipbench.drivers import train
    from chipbench.programs import data_mesh

    tr = cell.traffic
    step, replicated, bspec = train.build_step(cell.config, tr, data_mesh(devices))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        reference.param_shapes(cell.config),
        is_leaf=lambda s: isinstance(s, tuple) and all(isinstance(e, int) for e in s))
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        jax.eval_shape(init_train_state, params))
    tok = jax.ShapeDtypeStruct((tr["batch_per_chip"] * len(devices), tr["seq_len"]),
                               jnp.int32, sharding=bspec)
    return step.lower(state, {"tokens": tok, "labels": tok}).compile()


def compile_allreduce(cell, devices):
    """The AllReduce driver's call, compiled for ``devices`` from shapes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from chipbench.drivers import allreduce

    tr = cell.traffic
    mesh = allreduce._mesh(devices)
    x = jax.ShapeDtypeStruct((len(devices), tr["bytes_per_rank"] // 4), jnp.float32,
                             sharding=NamedSharding(mesh, P("data", None)))
    return allreduce.build(mesh, tr["schedule"]).lower(x).compile()


COMPILERS = {"train": compile_train, "allreduce": compile_allreduce}


def program_text(cell, devices) -> str:
    """The text of ``cell``'s program compiled for ``devices``, past the
    persistent compilation cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return COMPILERS[cell.traffic["driver"]](cell, devices).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@functools.lru_cache(maxsize=2)
def _chains(root: str, cell_name: str, chips: int) -> dict[str, tuple[str, ...]]:
    import jax
    from chipbench import bench

    cell = bench.load_cell(cell_name, Path(root))
    return scope_chains(program_text(cell, jax.devices()[:chips]))


def chains_for(run) -> dict[str, tuple[str, ...]]:
    """Scope chains of the instructions of the traced run's program."""
    return _chains(str(run.cell.bench_dir.parent), run.cell.name, run.chips)


def ms_per(run, scope: str, unit: str) -> float | None:
    """Milliseconds of device self time under ``scope`` per ``unit`` of
    the window's counts (``steps``, ``calls``); None where no instruction
    of the program carries the scope, as in a program without it.  A scope
    whose instructions XLA fused into others' reads 0: a fusion carries
    the ``op_name`` of its root."""
    n = run.window.counts.get(unit)
    chains = chains_for(run) if n else {}
    if not any(scope in chain for chain in chains.values()):
        return None
    return 1e3 * time_under(run.trace.op_s, chains, scope) / n
