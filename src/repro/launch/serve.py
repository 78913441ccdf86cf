"""Serving driver: one CLI, two frontends (``--frontend``, names in
``FRONTENDS``).

* ``llm`` — batched LLM request loop over prefill + decode
  (``serving/engine.py``).  CPU-scale with --smoke (reduced configs); the
  dry-run proves the same serve_step lowerings on the production meshes.
* ``scoring`` — the online feature-scoring tier (``serving/frontend.py``
  via ``ScoringPipeline.serve``): open-loop Poisson request admission,
  dynamic batching with a ``--max-wait-ms`` deadline, write-behind
  persistence underneath, per-request latency quantiles reported.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \\
        --requests 8 --prompt-len 32 --new-tokens 32
    PYTHONPATH=src python -m repro.launch.serve --frontend scoring \\
        --regime fraud --requests 5000 --load 20000
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, load_config, load_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import backbone
from repro.serving.engine import make_serve_step, sample_token

# Serving frontends this CLI can drive; README.md documents each and
# scripts/check_docs.py lints the two lists against each other (same
# pattern as LAYOUTS / EVICTION / BACKENDS).
FRONTENDS = ("llm", "scoring")


def _serve_llm(args) -> None:
    run = (load_smoke_config if args.smoke else load_config)(args.arch)
    cfg = run.model
    dtype = jnp.float32 if args.smoke else jnp.bfloat16
    params = backbone.init_params(cfg, jax.random.PRNGKey(args.seed), dtype)

    if not cfg.causal:
        # encoder-only: serve = full-sequence classification
        encode = jax.jit(make_serve_step(run, "prefill",
                                         compute_dtype=dtype))
        rng = np.random.default_rng(args.seed)
        batch = {"frames": jnp.asarray(rng.normal(
            size=(args.batch, args.prompt_len, cfg.frame_dim)), dtype),
            "labels": jnp.zeros((args.batch, args.prompt_len), jnp.int32)}
        t0 = time.perf_counter()
        logits = jax.block_until_ready(encode(params, batch))
        print(f"encoded {args.batch}x{args.prompt_len} frames -> "
              f"{logits.shape} in {time.perf_counter() - t0:.2f}s")
        return

    prefill = jax.jit(make_serve_step(
        run, "prefill", compute_dtype=dtype,
        max_len=args.prompt_len + args.new_tokens))
    decode = jax.jit(make_serve_step(run, "decode", compute_dtype=dtype))

    rng = np.random.default_rng(args.seed)
    n_batches = -(-args.requests // args.batch)
    total_new = 0
    t_pre = t_dec = 0.0
    for b in range(n_batches):
        prompts = jnp.asarray(rng.integers(
            0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)
        extra = {}
        if cfg.family == "vlm":
            extra["image_embeds"] = jnp.asarray(rng.normal(
                size=(args.batch, cfg.num_vision_tokens, cfg.d_model)), dtype)
        t0 = time.perf_counter()
        logits, state = jax.block_until_ready(
            prefill(params, {"tokens": prompts, **extra}))
        t_pre += time.perf_counter() - t0
        tok = sample_token(logits, jax.random.PRNGKey(b),
                           temperature=args.temperature,
                           vocab_size=cfg.vocab_size)
        t0 = time.perf_counter()
        for i in range(args.new_tokens - 1):
            logits, state = decode(params, state, tok)
            tok = sample_token(logits, jax.random.PRNGKey(1000 * b + i),
                               temperature=args.temperature,
                               vocab_size=cfg.vocab_size)
        jax.block_until_ready(tok)
        t_dec += time.perf_counter() - t0
        total_new += args.batch * args.new_tokens
        print(f"batch {b}: prefill ok, decoded {args.new_tokens} tokens")

    print(f"\nserved {n_batches * args.batch} requests | "
          f"prefill {t_pre:.2f}s | decode {t_dec:.2f}s "
          f"({total_new / max(t_dec, 1e-9):,.0f} tok/s incl. compile)")


def _serve_scoring(args) -> None:
    from repro.serving.frontend import poisson_arrivals
    from repro.serving.pipeline import ScoringPipeline, init_scorer
    from repro.features.spec import ProfileSpec
    from repro.streaming.workload import REGIMES, generate_regime

    if args.regime not in REGIMES:
        raise SystemExit(f"unknown regime {args.regime!r}; choose from "
                         f"{tuple(REGIMES)}")
    spec = ProfileSpec(windows=(60.0, 3600.0, 86400.0),
                       write_budget_per_min=0.1 / 60.0, variance_alpha=1.0)
    stream = generate_regime(args.regime, seed=args.seed,
                             n_events=args.requests)
    pipe = ScoringPipeline.build(spec, stream.spec.n_keys, mode="fast")
    pipe.scorer = init_scorer(jax.random.PRNGKey(1), spec.feature_dim)
    n = len(stream)
    arrivals = poisson_arrivals(n, args.load, seed=args.seed) \
        if args.load > 0 else np.zeros(n)
    residency = args.residency if args.residency > 0 else None
    # warmup: compile the dispatch programs on a short burst prefix so the
    # reported latencies measure serving, not tracing
    w = min(4 * args.batch, n)
    wsink = pipe.make_sink()
    pipe.serve(stream.key[:w], stream.q[:w], stream.t[:w],
               arrival_s=np.zeros(w), batch=args.batch,
               max_wait_s=args.max_wait_ms / 1e3,
               rng=jax.random.PRNGKey(args.seed), sink=wsink,
               residency=residency)
    wsink.close()
    sink = pipe.make_sink()
    t0 = time.perf_counter()
    res = pipe.serve(stream.key, stream.q, stream.t, arrival_s=arrivals,
                     batch=args.batch, max_wait_s=args.max_wait_ms / 1e3,
                     rng=jax.random.PRNGKey(args.seed), sink=sink,
                     residency=residency)
    stats = sink.flush()
    wall = time.perf_counter() - t0
    sink.close()
    q = res.latency_quantiles()
    st = res.stats
    print(f"served {n} score requests over regime={args.regime} "
          f"(offered {'burst' if args.load <= 0 else f'{args.load:,.0f}/s'},"
          f" batch<={args.batch}, deadline {args.max_wait_ms}ms)")
    print(f"  latency p50 {q['p50'] * 1e3:.3f}ms | p99 "
          f"{q['p99'] * 1e3:.3f}ms | p999 {q['p999'] * 1e3:.3f}ms")
    print(f"  dispatches {st.dispatches} (full {st.full_batches}, deadline "
          f"{st.deadline_batches}) | mean batch "
          f"{st.events / max(st.dispatches, 1):.1f} | max queue "
          f"{st.max_queue}")
    if residency:
        print(f"  residency: prefetched {st.prefetch_issued} "
              f"(hits {st.prefetch_hits}, rehydrations "
              f"{st.prefetch_rehydrations}), demand reads {st.demand_reads}")
    print(f"  persistence: {stats['puts']} puts "
          f"({stats['puts'] / n:.4f}/event) | wall {wall:.2f}s "
          f"({n / wall:,.0f} events/s)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--frontend", default="llm", choices=FRONTENDS,
                    help="llm: prefill+decode token serving; scoring: "
                         "open-loop feature-scoring tier "
                         "(serving/frontend.py)")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    # llm frontend
    ap.add_argument("--arch", default="qwen3-4b", choices=ARCH_IDS)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    # scoring frontend
    ap.add_argument("--regime", default="fraud",
                    help="Table 2 workload regime (streaming/workload.py)")
    ap.add_argument("--load", type=float, default=0.0,
                    help="offered load, events/s (<=0: burst — all "
                         "requests arrive at once)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="partial-batch dispatch deadline")
    ap.add_argument("--residency", type=int, default=0,
                    help="resident-slot budget (0: dense state)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.frontend == "scoring":
        if args.requests == 8:          # llm-sized default: too small to
            args.requests = 4096        # exercise the batcher
        if args.batch == 4:
            args.batch = 256
        _serve_scoring(args)
    else:
        _serve_llm(args)


if __name__ == "__main__":
    main()
