"""End-to-end driver: train a ~100M VLM whose input pipeline is the paper's
on-device JPEG decoder (the deployment the paper motivates).

    PYTHONPATH=src python examples/train_vlm_jpeg_pipeline.py --steps 60

Per step: a batch of compressed JPEGs (only ~100s of KB) is shipped to the
device, entropy-decoded in parallel, IDCT'd, patchified, and fed as vision
tokens to the LLaVA-style backbone next to a synthetic caption; a standard
next-token loss trains the model. Checkpoints + resume supported.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.data.jpeg_pipeline import JpegVisionPipeline
from repro.data.tokens import SyntheticTokens
from repro.jpeg.encoder import DatasetSpec, build_dataset
from repro.models.model import forward_train, init_params
from repro.train.checkpoint import latest_step, restore_checkpoint, \
    save_checkpoint
from repro.train.optimizer import AdamWConfig, init_opt_state, adamw_update
from repro.train.schedule import warmup_cosine
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--caption-len", type=int, default=48)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", default="none")
    args = ap.parse_args()

    # backbone: llava smoke config scaled up a bit (~100M with embeddings)
    cfg = get_smoke_config("llava-next-mistral-7b")
    cfg = dataclasses.replace(cfg, d_model=512, n_heads=8, n_kv_heads=4,
                              head_dim=64, d_ff=1408, n_periods=6,
                              vocab=8192, n_patches=192, attn_chunk=256)
    print(f"backbone ~{cfg.param_count()/1e6:.0f}M params")

    # image source: synthetic "video" dataset, 128x96 -> 192 patches @ p=8
    ds = build_dataset(DatasetSpec("vlmtrain", n_images=64, width=128,
                                   height=96, quality=80))
    pipe = JpegVisionPipeline(patch=8, embed_dim=1024, chunk_bits=512)

    model = init_params(jax.random.key(0), cfg)
    opt_cfg = AdamWConfig(lr=1e-3)
    opt_state = init_opt_state(model.params, opt_cfg)
    params = model.params
    toks = SyntheticTokens(cfg.vocab, args.caption_len, args.batch)

    @jax.jit
    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: forward_train(p, cfg, batch), has_aux=True)(params)
        lr = warmup_cosine(opt_state.step, warmup=10, total=args.steps)
        params, opt_state, om = adamw_update(params, grads, opt_state,
                                             opt_cfg, lr)
        return params, opt_state, loss

    start = 0
    if args.resume == "auto" and args.ckpt_dir:
        ls = latest_step(args.ckpt_dir)
        if ls:
            r = restore_checkpoint(args.ckpt_dir, ls,
                                   {"params": params, "opt": opt_state})
            params, opt_state, start = r["params"], r["opt"], ls
            print(f"resumed from step {ls}")

    n_img = len(ds.jpeg_bytes)
    decode_ms = 0.0
    for i in range(start, args.steps):
        j = (i * args.batch) % (n_img - args.batch + 1)
        t0 = time.perf_counter()
        patches, stats = pipe.patches_for(ds.jpeg_bytes[j : j + args.batch])
        patches.block_until_ready()
        decode_ms += (time.perf_counter() - t0) * 1e3
        tb = toks.batch_at(i)
        batch = {
            "tokens": jnp.asarray(tb["tokens"]),
            "labels": jnp.asarray(tb["labels"]),
            "patches": patches[:, : cfg.n_patches, :],
        }
        params, opt_state, loss = step(params, opt_state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={float(loss):.4f} "
                  f"(jpeg decode {decode_ms/ (i - start + 1):.1f} ms/step, "
                  f"{stats.transfer_saving:.1f}x transfer saving)", flush=True)
        if args.ckpt_dir and (i + 1) % 25 == 0:
            save_checkpoint(args.ckpt_dir, i + 1,
                            {"params": params, "opt": opt_state})
    print("done")


if __name__ == "__main__":
    main()
