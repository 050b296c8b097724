"""Serving launcher: batched requests with failure-aware strategies.

``serve()`` is the entry point; ``main()`` is a thin command line over it.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --requests 4 --max-new 16 --strategy r2ccl --fail-at-step 5
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.failures import Failure, FailureType
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_config, get_smoke_config, init_params
from repro.serving import Request, RequestResult, ServingEngine


@dataclasses.dataclass
class ServeResult:
    results: list[RequestResult]
    engine: ServingEngine
    warmup_s: float                # the untimed first batch, compiles included


def _encoder_only_reason(name: str) -> str:
    return (f"{name} is encoder-only: it has no causal decoder and no KV "
            "cache, so there is nothing to decode token by token")


def serve(
    cfg: ModelConfig,
    *,
    requests: int = 4,
    prompt_len: int = 32,
    max_new: int = 16,
    context_len: int | None = None,
    strategy: str = "r2ccl",
    fail_at_step: int | None = None,
    fail_node: int = 0,
    fail_rail: int = 0,
) -> ServeResult:
    """Serve ``requests`` random prompts with greedy decoding, injecting a
    NIC failure at decode step ``fail_at_step`` if it is set.

    The same batch runs once first without a failure, so that prefill and
    decode are compiled before the measured batch; its wall time is
    ``warmup_s``.  ``context_len`` defaults to ``prompt_len + max_new``.
    """
    if cfg.encoder_only:
        raise ValueError(_encoder_only_reason(cfg.name))
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = ServingEngine(cfg, params,
                           context_len=context_len or prompt_len + max_new,
                           strategy=strategy)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, prompt_len),
                    max_new_tokens=max_new)
            for _ in range(requests)]
    failure = None
    if fail_at_step is not None:
        failure = Failure(FailureType.NIC_HARDWARE, fail_node, fail_rail)

    t0 = time.perf_counter()
    engine.run_batch(reqs)
    warmup_s = time.perf_counter() - t0
    results = engine.run_batch(reqs, fail_at_step=fail_at_step, failure=failure)
    return ServeResult(results, engine, warmup_s)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--context-len", type=int, default=128)
    ap.add_argument("--strategy", default="r2ccl",
                    choices=["r2ccl", "restart", "reroute", "dejavu"])
    ap.add_argument("--fail-at-step", type=int, default=None)
    ap.add_argument("--fail-node", type=int, default=0)
    ap.add_argument("--fail-rail", type=int, default=0)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.encoder_only:
        raise SystemExit(_encoder_only_reason(args.arch))
    enable_compile_cache()
    out = serve(cfg, requests=args.requests, prompt_len=args.prompt_len,
                max_new=args.max_new, context_len=args.context_len,
                strategy=args.strategy, fail_at_step=args.fail_at_step,
                fail_node=args.fail_node, fail_rail=args.fail_rail)
    results = out.results
    for i, r in enumerate(results):
        print(f"req {i}: ttft={r.ttft*1e3:.1f}ms tpot={r.tpot*1e3:.1f}ms "
              f"total={r.total_latency:.3f}s failovers={r.failovers} "
              f"tokens={r.tokens[:8]}...")
    print(json.dumps({
        "strategy": args.strategy,
        "mean_ttft_ms": float(np.mean([r.ttft for r in results]) * 1e3),
        "mean_tpot_ms": float(np.mean([r.tpot for r in results]) * 1e3),
        "total_s": results[0].total_latency,
    }))


if __name__ == "__main__":
    main()
