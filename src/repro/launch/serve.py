"""Serving driver: batched prefill + decode loop on a reduced config.

Demonstrates the serving entry points actually executing:

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --batch 4 --prompt-len 32 --gen 16

``--metrics-port`` keeps the process alive after the demo loop with
``/healthz`` and ``/metrics`` endpoints rendering the ``repro.obs``
registry snapshot — prefill/decode timings, token counters, and (once
this becomes the ingest tier of the roadmap's hierarchical aggregation)
worker liveness and byte ledgers, all through the same registry the FL
transports feed.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ARCH_IDS, get_smoke_config
from repro.models.build import build_model
from repro.models.encdec import EncDec
from repro.obs import get_registry


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32, dest="prompt_len")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-port", type=int, default=None,
                    dest="metrics_port",
                    help="serve /healthz + /metrics (the obs.meters "
                         "snapshot) on this port and stay up after the "
                         "demo loop (0 picks a free port)")
    args = ap.parse_args(argv)

    meters = get_registry()
    http = None
    if args.metrics_port is not None:
        from repro.obs.http import ObsHTTPServer
        http = ObsHTTPServer(port=args.metrics_port)
        print(f"metrics -> {http.url}/metrics  health -> {http.url}/healthz")

    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    meters.gauge("serve.batch").set(args.batch)
    meters.gauge("serve.prompt_len").set(args.prompt_len)
    # independent streams: reusing one key for init AND data correlates the
    # sampled prompt with the weights it is fed through
    k_init, k_tok, k_frames = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    params = model.init(k_init)
    tokens = jax.random.randint(k_tok, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    cache_len = args.prompt_len + args.gen

    t0 = time.time()
    if isinstance(model, EncDec):
        frames = jax.random.normal(k_frames, (args.batch, cfg.num_mm_tokens,
                                              cfg.d_model))
        prefill = jax.jit(lambda p, f, t: model.prefill(p, f, t, cache_len))
        logits, cache, t = prefill(params, frames, tokens)
    else:
        prefill = jax.jit(lambda p, t: model.prefill(p, t, cache_len))
        logits, cache, t = prefill(params, tokens)
    prefill_s = time.time() - t0
    meters.histogram("serve.prefill_s").observe(prefill_s)
    meters.counter("serve.prefills").inc()
    print(f"prefill: batch={args.batch} len={args.prompt_len} "
          f"({prefill_s:.1f}s incl. compile)")

    decode = jax.jit(model.decode_step)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        step_t0 = time.time()
        logits, cache = decode(params, cache, tok, t + i)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
        meters.histogram("serve.decode_step_s").observe(time.time() - step_t0)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    meters.counter("serve.tokens").inc(args.gen * args.batch)
    meters.gauge("serve.tokens_per_s").set(args.gen * args.batch / max(dt, 1e-9))
    gen = jnp.stack(out, axis=1)
    print(f"decoded {args.gen} tokens x {args.batch} seqs in {dt:.2f}s "
          f"({args.gen*args.batch/max(dt,1e-9):.1f} tok/s incl. compile)")
    print("sample token ids:", gen[0, :12].tolist())
    assert bool(jnp.all(jnp.isfinite(logits))), "NaN logits"
    print("serve OK")
    if http is not None:
        print("serving metrics until interrupted (ctrl-c to exit)")
        try:
            while True:
                time.sleep(1.0)
        except KeyboardInterrupt:
            pass
        finally:
            http.stop()


if __name__ == "__main__":
    main()
