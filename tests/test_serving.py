"""Serving engine: batched decode, failure strategies, latency accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.failures import Failure, FailureType
from repro.models import get_smoke_config, init_caches, init_model
from repro.serving import Request, ServingEngine
from repro.serving.engine import make_decode_fn, make_prefill_fn


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("glm4-9b")
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(cfg, n=2, plen=12, new=6):
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, plen),
                    max_new_tokens=new) for _ in range(n)]


def test_greedy_decode_deterministic(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, context_len=64, strategy="r2ccl")
    r1 = eng.run_batch(_reqs(cfg))
    r2 = eng.run_batch(_reqs(cfg))
    assert r1[0].tokens == r2[0].tokens
    assert len(r1[0].tokens) == 6


def _greedy_per_request(cfg, params, reqs, context_len):
    """The batch decoded by a plain greedy loop that reads each request's
    token from the device on its own."""
    T = max(len(r.prompt) for r in reqs)
    toks = np.zeros((len(reqs), T), np.int32)
    for i, r in enumerate(reqs):
        toks[i, T - len(r.prompt):] = r.prompt
    caches = init_caches(cfg, len(reqs), context_len, dtype=jnp.float32)
    tok, caches = make_prefill_fn(cfg)(params, {"tokens": jnp.asarray(toks)},
                                       caches)
    decode = make_decode_fn(cfg)
    out = [[int(tok[i])] for i in range(len(reqs))]
    for _ in range(max(r.max_new_tokens for r in reqs) - 1):
        tok, caches = decode(params, tok, caches)
        for i, r in enumerate(reqs):
            if len(out[i]) < r.max_new_tokens:
                out[i].append(int(tok[i]))
    return out


@pytest.mark.parametrize("new", [(3, 6), (5, 5, 5)])
@pytest.mark.parametrize("strategy,fail_at", [("r2ccl", None), ("restart", 2)])
def test_one_token_transfer_per_step(setup, monkeypatch, new, strategy, fail_at):
    """``run_batch`` copies the first token and each decode step's tokens
    to the host in one transfer each, not one per request, and serves the
    tokens a per-request greedy loop reads."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, 12), max_new_tokens=n)
            for n in new]
    eng = ServingEngine(cfg, params, context_len=64, strategy=strategy)
    fail = Failure(FailureType.NIC_HARDWARE, 0, 0) if fail_at is not None else None
    eng.run_batch(reqs, fail_at_step=fail_at, failure=fail)   # compiles
    array_type = type(jnp.zeros(()))
    to_host = array_type._value                 # every jax.Array -> numpy copy
    transfers = []

    def counted(self):
        transfers.append(self.shape)
        return to_host.fget(self)

    monkeypatch.setattr(array_type, "_value", property(counted))
    results = eng.run_batch(reqs, fail_at_step=fail_at, failure=fail)
    monkeypatch.undo()
    # the prefill's token, then one per decode step, each the whole batch's
    assert transfers == [(len(new),)] * max(new)
    assert [r.tokens for r in results] == _greedy_per_request(cfg, params, reqs, 64)
    assert [len(r.tokens) for r in results] == list(new)
    assert all(r.failovers == (fail_at is not None) for r in results)


def test_r2ccl_continues_through_failure(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, context_len=64, strategy="r2ccl")
    fail = Failure(FailureType.NIC_HARDWARE, 0, 0)
    healthy = eng.run_batch(_reqs(cfg))
    eng2 = ServingEngine(cfg, params, context_len=64, strategy="r2ccl")
    failed = eng2.run_batch(_reqs(cfg), fail_at_step=2, failure=fail)
    # same tokens (lossless), tiny latency overhead
    assert healthy[0].tokens == failed[0].tokens
    assert failed[0].failovers == 1
    assert failed[0].total_latency < healthy[0].total_latency * 1.5


def test_restart_pays_full_penalty(setup):
    cfg, params = setup
    fail = Failure(FailureType.NIC_HARDWARE, 0, 0)
    e_restart = ServingEngine(cfg, params, context_len=64, strategy="restart")
    e_r2 = ServingEngine(cfg, params, context_len=64, strategy="r2ccl")
    r_restart = e_restart.run_batch(_reqs(cfg), fail_at_step=2, failure=fail)
    r_r2 = e_r2.run_batch(_reqs(cfg), fail_at_step=2, failure=fail)
    assert r_restart[0].total_latency > r_r2[0].total_latency + 30.0  # 35 s restart
    assert r_restart[0].tokens == r_r2[0].tokens                      # same result


def test_unsupported_failure_rejected(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, context_len=64, strategy="r2ccl")
    bad = Failure(FailureType.SWITCH_OUTAGE, 0, -1)
    assert eng.inject_failure(bad) is False
    assert len(eng.failure_state.unsupported) == 1


def test_r2ccl_hiccup_is_control_plane_ledger(setup):
    """The r2ccl failover hiccup is the recovery pipeline's ledger total,
    and a failure on a node outside the replica's span falls back to the
    constant instead of crashing (regression: used to IndexError)."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, context_len=64, strategy="r2ccl")
    assert len(eng.control_plane.cluster.nodes) == 2    # pp=2 replica span
    fail = Failure(FailureType.NIC_HARDWARE, 1, 0)
    res = eng.run_batch(_reqs(cfg), fail_at_step=2, failure=fail)
    assert res[0].failovers == 1
    assert eng.last_recovery is not None
    assert eng.last_recovery.total == sum(eng.last_recovery.stages.values())
    # out-of-replica node: constant-hiccup fallback, no crash
    eng2 = ServingEngine(cfg, params, context_len=64, strategy="r2ccl")
    far = Failure(FailureType.NIC_HARDWARE, 5, 0)
    res2 = eng2.run_batch(_reqs(cfg), fail_at_step=2, failure=far)
    assert res2[0].failovers == 1
    assert eng2.last_recovery is None


def test_ttft_before_tpot(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, context_len=64, strategy="r2ccl")
    res = eng.run_batch(_reqs(cfg))
    assert res[0].ttft > 0 and res[0].tpot > 0
    assert res[0].total_latency >= res[0].ttft


def test_serve_trace(setup):
    from repro.serving import serve_trace
    cfg, params = setup
    eng = ServingEngine(cfg, params, context_len=64, strategy="r2ccl")
    res = serve_trace(eng, qps=2.0, duration=3.0, prompt_len=12,
                      max_new_tokens=4)
    assert res.completed >= 4
    assert res.ttft_p95 >= res.ttft_p50 > 0
    assert res.tpot_p50 > 0


def test_serve_trace_failure_strategies_ordering(setup):
    """Under the same mid-trace failure, r2ccl's p95 TTFT must beat restart."""
    from repro.serving import serve_trace
    cfg, params = setup
    outs = {}
    for strat in ("r2ccl", "restart"):
        eng = ServingEngine(cfg, params, context_len=64, strategy=strat)
        outs[strat] = serve_trace(
            eng, qps=2.0, duration=3.0, prompt_len=12, max_new_tokens=4,
            fail_time=1.0,
            failure=Failure(FailureType.NIC_HARDWARE, 0, 0))
    assert outs["r2ccl"].ttft_p95 < outs["restart"].ttft_p95
    assert outs["r2ccl"].failovers == 1


def test_hiccup_attribution_from_trace(setup):
    """Hiccup attribution comes from trace stage spans alone and matches
    the ledger's stage totals; fractions sum to 1; diagnose (probe timeout
    + broadcast) dominates the clean NIC-down budget."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, context_len=64, strategy="r2ccl")
    assert eng.hiccup_attribution() == {}          # nothing happened yet
    fail = Failure(FailureType.NIC_HARDWARE, 1, 0)
    eng.run_batch(_reqs(cfg), fail_at_step=2, failure=fail)
    attr = eng.hiccup_attribution()
    assert attr == pytest.approx(
        {k: v for k, v in eng.last_recovery.stages.items() if v > 0})
    frac = eng.hiccup_attribution(normalize=True)
    assert sum(frac.values()) == pytest.approx(1.0)
    assert max(frac, key=frac.get) == "diagnose"
    # the failure injection itself is on the trace too
    kinds = {r["type"] for r in eng.trace.records}
    assert {"failure", "stage", "transition"} <= kinds
