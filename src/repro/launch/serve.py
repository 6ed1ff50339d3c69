"""Serving driver: continuous-batching split-model serving on synthetic
open-loop traffic (see docs/SERVING.md).

Thin CLI over :class:`repro.serve.ServeEngine`: builds the seeded load
(``repro.serve.loadgen``), serves it through the fixed-capacity lane
array with the compressed uplink and the quantized decode activation
ring, and prints the production-shaped numbers — requests/sec,
tokens/sec, p50/p99 token latency, exact wire bytes per token.
Token-aligned (fusion="add") archs run the engine; cross-attention
families (vlm / audio) exchange their memory once at prefill and decode
entirely on Party B, so they fall back to the sequential
:func:`repro.serve.naive_generate` loop (reported as such).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \\
      --requests 32 --capacity 8 --prompt-len 16 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models import vfl
from ..serve import (LoadSpec, ServeConfig, ServeEngine, make_naive_fns,
                     naive_generate, synth_requests)


def _percentiles(comps):
    lats = []
    for c in comps:
        prev = c.arrival
        for t in c.token_times:
            lats.append(t - prev)
            prev = t
    ms = 1e3 * np.asarray(lats)
    return float(np.percentile(ms, 50)), float(np.percentile(ms, 99))


def serve_engine(args, cfg, params):
    scfg = ServeConfig(capacity=args.capacity, prompt_len=args.prompt_len,
                       max_new_tokens=args.gen,
                       compression="" if args.fp32_wire else "int8",
                       cache_dtype=args.cache_dtype,
                       refresh_every=args.refresh_every, seed=args.seed)
    spec = LoadSpec(n_requests=args.requests, rate=args.rate,
                    prompt_len=args.prompt_len, max_new_tokens=args.gen,
                    min_new_tokens=max(1, args.gen // 4), seed=args.seed)
    eng = ServeEngine(params, cfg, scfg)
    t0 = time.perf_counter()
    eng.warm()
    print(f"warm (compile) {time.perf_counter() - t0:.1f} s")
    comps, stats = eng.run(synth_requests(spec, cfg))

    n_tok = stats["total_tokens"]
    dur = stats["virtual_duration_s"]
    p50, p99 = _percentiles(comps)
    up, down = stats["wire_up_bytes"], stats["wire_down_bytes"]
    print(f"arch={cfg.name} capacity={scfg.capacity} "
          f"wire={scfg.compression or 'fp32'} ring={scfg.cache_dtype} "
          f"R={scfg.refresh_every}")
    print(f"{stats['n_requests']} requests, {n_tok} tokens in {dur:.2f} s "
          f"(virtual) -> {stats['n_requests'] / dur:.1f} req/s, "
          f"{n_tok / dur:.0f} tok/s")
    print(f"p50 {p50:.2f} ms/token | p99 {p99:.2f} ms/token")
    print(f"wire: {up} B up + {down} B down = {(up + down) / n_tok:.1f} "
          f"B/token ({eng.step_up_bytes} B per decode uplink row)")
    print("first request's token ids:", comps[0].tokens[:16])
    return comps


def serve_naive(args, cfg, params):
    """Sequential fallback for cross-attn families: the cut memory
    crosses once at prefill; decode is Party-B-local."""
    B, S = 1, args.prompt_len
    rng = np.random.default_rng(args.seed)
    fns = make_naive_fns(cfg, S + args.gen)
    batch0 = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32))}
    if cfg.family == "vlm":
        batch0["patches"] = jnp.asarray(rng.normal(
            size=(B, cfg.n_patches, cfg.d_frontend)).astype(np.float32))
    else:
        batch0["frames"] = jnp.asarray(rng.normal(
            size=(B, S, cfg.d_frontend)).astype(np.float32))
    naive_generate(params, cfg, batch0, args.gen, fns=fns)  # warm
    walls = []
    toks = None
    for _ in range(args.requests):
        t0 = time.perf_counter()
        toks = naive_generate(params, cfg, batch0, args.gen, fns=fns)
        jax.block_until_ready(toks)
        walls.append(time.perf_counter() - t0)
    total = sum(walls)
    print(f"arch={cfg.name} ({cfg.family}): cross-attn family — memory "
          f"crosses once at prefill; sequential naive_generate loop")
    print(f"{args.requests} requests x {args.gen} tokens in {total:.2f} s "
          f"-> {args.requests * args.gen / total:.0f} tok/s, "
          f"{total / args.requests / args.gen * 1e3:.1f} ms/token")
    print("generated token ids (first request):",
          np.asarray(toks)[0][:16])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop arrival rate in req/s (0 = closed "
                         "burst)")
    ap.add_argument("--capacity", type=int, default=8,
                    help="concurrent decode lanes")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cache-dtype", default="int8",
                    choices=("float32", "bfloat16", "int8", "int4"),
                    help="decode activation ring at-rest storage")
    ap.add_argument("--refresh-every", type=int, default=1,
                    help="uplink cadence R: exchange every R-th decode "
                         "step, serve Party B from the stale ring row in "
                         "between")
    ap.add_argument("--fp32-wire", action="store_true",
                    help="identity uplink codec (bit-exact vs the "
                         "sequential loop) instead of int8")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="full config (do NOT use on CPU)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    params = vfl.init_all(jax.random.PRNGKey(args.seed), cfg)
    if cfg.vfl_split.fusion == "add":
        serve_engine(args, cfg, params)
    else:
        serve_naive(args, cfg, params)


if __name__ == "__main__":
    from .compile_cache import enable
    enable()
    main()
