"""Batched serving driver: prefill a batch of prompts, then decode tokens.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --batch 4 \
      --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from ..configs import ARCH_IDS, get_smoke_config
    from ..models.model import init_caches, init_params
    from ..serve.step import make_decode_step, make_prefill_step
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3-8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--jpeg-stream", type=int, default=0, metavar="N",
                    help="dry-run the JPEG input pipeline over N distinct "
                         "batches first and report the streaming decode "
                         "stats (compile-once buckets, warm-step ms)")
    ap.add_argument("--decode-serve", type=int, default=0, metavar="N",
                    help="dry-run the continuous-batching decode service "
                         "with N open-loop requests first and report its "
                         "serve stats (occupancy, deadline misses, "
                         "admitted buckets)")
    ap.add_argument("--serve-rate", type=float, default=0.0, metavar="IPS",
                    help="Poisson arrival rate for --decode-serve "
                         "(images/sec; 0 = saturated backlog drain)")
    ap.add_argument("--serve-slo", type=float, default=250.0, metavar="MS",
                    help="per-request deadline for --decode-serve")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator for a multi-host "
                         "launch (or REPRO_COORDINATOR); the JPEG stream "
                         "is then fed per host")
    ap.add_argument("--processes", type=int, default=None,
                    help="total process count of the multi-host launch "
                         "(or REPRO_NUM_PROCESSES)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this host's process id (or REPRO_PROCESS_ID)")
    args = ap.parse_args()

    from .multihost import init_distributed
    ctx = init_distributed(args.coordinator, args.processes, args.process_id)

    if args.jpeg_stream:
        from .report import jpeg_stream_dryrun, render_decode_stats
        stats = jpeg_stream_dryrun(args.jpeg_stream, batch_size=args.batch,
                                   ctx=ctx)
        if ctx.is_main:
            print(render_decode_stats(stats), flush=True)

    if args.decode_serve and ctx.is_main:
        from .report import decode_serve_dryrun, render_serve_stats
        sstats, load = decode_serve_dryrun(args.decode_serve,
                                           batch_size=args.batch,
                                           rate_ips=args.serve_rate,
                                           slo_ms=args.serve_slo)
        print(render_serve_stats(sstats, load), flush=True)

    cfg = get_smoke_config(args.arch)
    max_len = args.prompt_len + args.gen + 8 + (
        cfg.n_patches if cfg.frontend == "vision" else 0)
    maxpos = max_len if cfg.norm == "layernorm" else 0
    model = init_params(jax.random.key(0), cfg, max_positions=maxpos)

    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)}
    if cfg.frontend == "vision":
        batch["patches"] = jnp.asarray(
            rng.normal(0, 1, (args.batch, cfg.n_patches, 1024)), jnp.bfloat16)
    if cfg.is_encdec:
        batch["frames"] = jnp.asarray(
            rng.normal(0, 1, (args.batch, cfg.enc_seq, 128)), jnp.bfloat16)

    caches = init_caches(cfg, args.batch, max_len)
    prefill = jax.jit(make_prefill_step(cfg, max_len))
    decode = jax.jit(make_decode_step(cfg))

    t0 = time.time()
    logits, caches = prefill(model.params, batch, caches)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    jax.block_until_ready(tok)
    t_prefill = time.time() - t0

    out = [tok]
    pos0 = args.prompt_len + (cfg.n_patches if cfg.frontend == "vision" else 0)
    t0 = time.time()
    for i in range(args.gen - 1):
        tok, _, caches = decode(model.params, tok, pos0 + i, caches)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    seq = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={args.batch}")
    print(f"prefill: {args.prompt_len} tokens x {args.batch} in "
          f"{t_prefill*1e3:.1f}ms")
    print(f"decode : {args.gen - 1} steps in {t_decode*1e3:.1f}ms "
          f"({(args.gen - 1) * args.batch / max(t_decode, 1e-9):.1f} tok/s)")
    print("sample token ids:", np.asarray(seq[0, :16]).tolist())


if __name__ == "__main__":
    main()
