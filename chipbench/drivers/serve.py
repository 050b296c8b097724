"""Window driver for open-loop serving.

Entry: ``repro.serving.ServingEngine.run_batch`` (the traffic's
``strategy``, no failure) over weights made on the device from the seed.
Set-up runs one batch of each size from 1 to ``max_batch``, so that every
prefill and decode program the window can use is compiled.

Traffic: requests with prompts of ``prompt_len`` tokens drawn from the
seed, each asking for ``max_new_tokens`` greedy tokens.  Their due times are
an open loop: the gaps are one fixed set of exponential draws with mean
``1 / rate_per_s`` (drawn once from ``arrival_set_seed``), the same for
every seed.  With static batches, the order of the gaps decides which
requests share a batch, and so the work: a seed that reordered them changed
the time per token by up to 20%.
The server takes up to ``max_batch`` requests that are due, in order, and
runs them as one batch; when none is due it waits for the next.  Requests
are timed from their due time: the time to the first token includes the
wait in the queue, and the time per output token is the rest of the request
over its tokens after the first.  The window lasts until every request due
in it is served, or ``drain_s`` past its close; one not served by then
counts as failed.

A sample of ``check_requests`` served requests, drawn from the seed, is
checked against the float32 reference once the window has closed.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any

import numpy as np

from chipbench import reference, seeds
from chipbench.bench import Check, Window


class RecordingClock:
    """The engine's clock seam, keeping every reading of the batch."""

    def __init__(self):
        self.reads: list[float] = []

    def __call__(self) -> float:
        t = time.perf_counter()
        self.reads.append(t)
        return t


@dataclasses.dataclass
class State:
    engine: Any
    clock: RecordingClock
    prompts: np.ndarray          # (R, prompt_len)
    offsets: np.ndarray          # (R,) due times from the window's start
    served: dict = dataclasses.field(default_factory=dict)  # request -> tokens


def arrival_offsets(traffic: dict, seconds: float) -> np.ndarray:
    """Due times, seconds after the window opens, of the requests due in a
    window of ``seconds``: the fixed set of gaps."""
    rate = traffic["rate_per_s"]
    count = int(round(rate * seconds))
    gaps = np.random.default_rng(traffic["arrival_set_seed"]).exponential(
        1.0 / rate, count)
    gaps *= seconds / gaps.sum()             # the set spans the window
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def requests(cfg: dict, traffic: dict, seed: int, seconds: float):
    """The due times and prompts of the requests of a window of ``seconds``."""
    offsets = arrival_offsets(traffic, seconds)
    prompts = seeds.rng(seed, "data").integers(
        0, cfg["vocab_size"], (len(offsets), traffic["prompt_len"]), dtype=np.int32)
    return offsets, prompts


def reseed(st: State, ctx, seconds: float, params=None) -> None:
    """Give a warmed set-up the requests of ``ctx.seed`` (and ``params``,
    where given) for a window of ``seconds``; its compiled programs stay."""
    st.offsets, st.prompts = requests(ctx.cell.config, ctx.cell.traffic,
                                      ctx.seed, seconds)
    st.served.clear()
    if params is not None:
        st.engine.params = params


def checked(st: State, ctx) -> list[int]:
    """The served requests that the comparison checks, drawn from the seed."""
    done = sorted(st.served)
    pick = seeds.rng(ctx.seed, "sample").choice(
        len(done), min(ctx.cell.traffic["check_requests"], len(done)), replace=False)
    return [done[i] for i in sorted(pick)]


def setup(ctx) -> State:
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.serving import Request, ServingEngine
    from chipbench.programs import model_config

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    params = reference.make_params(cfg, ctx.seed,
                                   SingleDeviceSharding(ctx.devices[0]))
    clock = RecordingClock()
    engine = ServingEngine(model_config(cfg), params,
                           context_len=tr["prompt_len"] + tr["max_new_tokens"],
                           strategy=tr["strategy"], clock=clock)
    offsets, prompts = requests(cfg, tr, ctx.seed, ctx.seconds)
    t0 = time.perf_counter()
    for b in range(1, tr["max_batch"] + 1):
        engine.run_batch([Request(prompt=prompts[i], max_new_tokens=2)
                          for i in range(b)])
    ctx.log(f"serve: warmed batch sizes 1-{tr['max_batch']} in "
            f"{time.perf_counter() - t0:.2f} s")
    jax.block_until_ready(params)
    return State(engine=engine, clock=clock, prompts=prompts, offsets=offsets)


def window(st: State, ctx, seconds: float) -> Window:
    from repro.serving import Request

    tr = ctx.cell.traffic
    new = tr["max_new_tokens"]
    n = len(st.offsets)
    ttft = np.full(n, np.nan)
    tpot = np.full(n, np.nan)
    batches, decode_wall, decode_steps, batch_sizes = 0, 0.0, 0, []
    head = 0
    t0 = time.perf_counter()
    due = t0 + st.offsets
    deadline = t0 + seconds + tr["drain_s"]
    while head < n:
        now = time.perf_counter()
        if now >= deadline:
            break
        if due[head] > now:
            with ctx.span("wait"):
                time.sleep(due[head] - now)
            continue
        stop = head
        while stop < n and stop - head < tr["max_batch"] and due[stop] <= now:
            stop += 1
        reqs = [Request(prompt=st.prompts[i], max_new_tokens=new)
                for i in range(head, stop)]
        st.clock.reads.clear()
        with ctx.span("batch"):
            results = st.engine.run_batch(reqs)
        done = time.perf_counter()
        first = st.clock.reads[1]           # prefill finished
        for i, r in zip(range(head, stop), results):
            st.served[i] = np.asarray(r.tokens, np.int32)
            ttft[i] = first - due[i]
            tpot[i] = (done - first) / (new - 1)
        batches += 1
        batch_sizes.append(stop - head)
        decode_wall += st.clock.reads[-1] - first
        decode_steps += new - 1
        head = stop
    elapsed = time.perf_counter() - t0
    if batches:
        ctx.log(f"serve: {n} requests due, {len(st.served)} served in {batches} "
                f"batches (mean {np.mean(batch_sizes):.2f}), decode step "
                f"{1e3 * decode_wall / decode_steps:.3f} ms on average")
    ok = np.isfinite(ttft)
    failed = int(n - ok.sum()) + sum(
        1 for t in st.served.values() if len(t) != new)
    e2e = {}
    if ok.any():
        e2e = {"ttft_p95_ms": float(np.percentile(ttft[ok], 95) * 1e3),
               "tpot_p95_ms": float(np.percentile(tpot[ok], 95) * 1e3)}
    return Window(seconds=elapsed, attempted=n, failed=failed, end_to_end=e2e,
                  counts={"batches": batches, "batch_sizes": batch_sizes,
                          "prompt_len": tr["prompt_len"],
                          "max_new_tokens": new,
                          "decode_wall_s": decode_wall,
                          "decode_steps": decode_steps})


def verify(st: State, ctx) -> list[Check]:
    from jax.sharding import SingleDeviceSharding

    cfg = ctx.cell.config
    ids = checked(st, ctx)
    st.engine = None
    gc.collect()
    if not ids:
        return [Check("logit_gap", float("inf"), ctx.limit("logit_gap"))]
    served = np.stack([st.served[i] for i in ids])
    if served.min() < 0 or served.max() >= cfg["vocab_size"]:
        return [Check("logit_gap", float("inf"), ctx.limit("logit_gap"))]
    params = reference.make_params(cfg, ctx.seed,
                                   SingleDeviceSharding(ctx.devices[0]))
    t0 = time.perf_counter()
    gaps = reference.served_gaps(params, st.prompts[ids], served, cfg)
    ctx.log(f"serve: reference over {len(ids)} requests x "
            f"{served.shape[1]} tokens took {time.perf_counter() - t0:.2f} s")
    return [Check("logit_gap", float(gaps.max()), ctx.limit("logit_gap"))]
