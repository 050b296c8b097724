"""Names of the program's profiler spans and device scopes.

Host spans are ``jax.profiler.TraceAnnotation(name, **args)`` around host
work.  They land on the host plane of the profiler trace, on the same clock
as the device's operations, and record nothing when no profiler session is
active (about a microsecond to enter and leave).  Spans of one batch share
its ``batch`` argument, the engine's running count of batches.

Device scopes are ``jax.named_scope(name)``.  They exist only while a
program is traced: each instruction that XLA compiles from the scoped code
carries the name in its ``op_name`` metadata, through ``jax.grad``,
``jax.checkpoint`` and ``lax.scan``, and the compiled program is otherwise
the same.  The innermost scope in an instruction's ``op_name`` assigns its
device time to a layer.

What reads each name (``chipbench/metrics/``, from a traced run):

========================  =====================================================
``r2ccl.serve.batch``     one ``ServingEngine.run_batch``; names idle gaps
``r2ccl.serve.alloc``     left-pad, cache allocation, tokens to the device:
                          ``alloc_ms.serve`` (self time per batch)
``r2ccl.serve.prefill``   the prefill call and its block; names idle gaps
``r2ccl.serve.decode``    one decode step, parent of the three below
``r2ccl.serve.dispatch``  the decode call until it returns:
                          ``dispatch_ms.serve`` (self time per decode step)
``r2ccl.serve.block``     ``block_until_ready`` on the step; names idle gaps
``r2ccl.serve.readback``  the step's tokens to the host in one transfer
                          (``transfers``), kept for the unfinished requests
                          (``reads``): ``readback_ms.serve`` (self time per
                          decode step)
``attention``             the GQA/MLA branch of a layer: ``attention_ms.train``
``ffn``                   the MLP/MoE of a layer: ``ffn_ms.train``
``sync``                  gradient wire casts, the collective, the metrics
                          ``pmean``: ``sync_ms.train``
``optimizer``             the AdamW update: ``optimizer_ms.train``
``pack``                  padding, reshapes, segment slices and the final
                          concatenate of a collective: ``pack_ms.allreduce``
``merge``                 a round's work besides its ``ppermute``
``<stem>``                one segment of a collective program, named by its
                          schedule's name before ``[`` (``ring_ar``,
                          ``partial_ar``): ``partial_ms.allreduce``
========================  =====================================================
"""

SERVE_BATCH = "r2ccl.serve.batch"
SERVE_ALLOC = "r2ccl.serve.alloc"
SERVE_PREFILL = "r2ccl.serve.prefill"
SERVE_DECODE = "r2ccl.serve.decode"
SERVE_DISPATCH = "r2ccl.serve.dispatch"
SERVE_BLOCK = "r2ccl.serve.block"
SERVE_READBACK = "r2ccl.serve.readback"

ATTENTION = "attention"
FFN = "ffn"
SYNC = "sync"
OPTIMIZER = "optimizer"
PACK = "pack"
MERGE = "merge"


def segment_scope(schedule_name: str) -> str:
    """``partial_ar[3]+bridge`` -> ``partial_ar``."""
    return schedule_name.split("[", 1)[0]
