"""Model assembly: decoder-only LMs, hybrid SSM/attention stacks, MoE,
encoder-decoder (whisper), and VLM (llava) — one composable implementation.

Layer layout = unrolled prefix + a periodic pattern scanned over periods
(stacked params), which keeps HLO size ~O(pattern) instead of O(n_layers):
essential for the 61-layer/256-expert dry-run compiles.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import shard
from .attention import (
    KVCache, MLACache, gqa_forward, init_kv_cache, init_mla_cache,
    make_gqa, make_mla, mla_forward,
)
from .config import ModelConfig
from .ffn import dense_ffn, make_dense_ffn, make_moe_ffn, moe_ffn
from .layers import ParamBuilder, apply_norm, make_norm
from .ssm import SSMCache, make_ssd, ssd_decode_step, ssd_forward


from .unroll import scan_unroll, unroll_n as _unroll  # noqa: F401


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def make_block(b: ParamBuilder, cfg: ModelConfig, spec, name: str,
               cross: bool = False):
    mixer, ffn = spec
    make_norm(b, f"{name}.norm1", cfg.d_model, cfg.norm)
    if mixer in ("attn", "attn_bidir"):
        make_gqa(b, cfg, f"{name}.attn")
    elif mixer == "mla":
        make_mla(b, cfg, f"{name}.attn")
    elif mixer == "ssm":
        make_ssd(b, cfg, f"{name}.ssm")
    if cross:
        make_norm(b, f"{name}.norm_x", cfg.d_model, cfg.norm)
        make_gqa(b, cfg, f"{name}.xattn")
    if ffn != "none":
        make_norm(b, f"{name}.norm2", cfg.d_model, cfg.norm)
        if ffn == "moe":
            make_moe_ffn(b, cfg, f"{name}.ffn")
        else:
            make_dense_ffn(b, cfg, f"{name}.ffn")


def block_forward(
    params: Dict, cfg: ModelConfig, spec, name: str, x: jnp.ndarray,
    positions: jnp.ndarray, *, cache=None, cache_pos=None,
    enc_out: Optional[jnp.ndarray] = None, decode: bool = False,
) -> Tuple[jnp.ndarray, Any, Dict]:
    mixer, ffn = spec
    aux: Dict = {}
    h = apply_norm(params, f"{name}.norm1", x, cfg.norm)
    new_cache = cache
    if mixer == "attn":
        h, new_cache = gqa_forward(params, cfg, f"{name}.attn", h, positions,
                                   causal=True, cache=cache,
                                   cache_pos=cache_pos)
    elif mixer == "attn_bidir":
        h, _ = gqa_forward(params, cfg, f"{name}.attn", h, positions,
                           causal=False)
    elif mixer == "mla":
        h, new_cache = mla_forward(params, cfg, f"{name}.attn", h, positions,
                                   cache=cache, cache_pos=cache_pos)
    elif mixer == "ssm":
        if decode:
            h, new_cache = ssd_decode_step(params, cfg, f"{name}.ssm", h, cache)
        else:
            h, new_cache = ssd_forward(params, cfg, f"{name}.ssm", h,
                                       cache=cache)
    x = x + h
    if enc_out is not None and f"{name}.norm_x.w" in params:
        h = apply_norm(params, f"{name}.norm_x", x, cfg.norm)
        h, _ = gqa_forward(params, cfg, f"{name}.xattn", h, positions,
                           kv_x=enc_out, use_rope=False)
        x = x + h
    if ffn != "none":
        h = apply_norm(params, f"{name}.norm2", x, cfg.norm)
        if ffn == "moe":
            h, aux = moe_ffn(params, cfg, f"{name}.ffn", h)
        else:
            h = dense_ffn(params, cfg, f"{name}.ffn", h)
        x = x + h
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

class Model(NamedTuple):
    params: Dict
    specs: Dict


def init_params(rng: Optional[jax.Array], cfg: ModelConfig,
                max_positions: int = 0, abstract: bool = False) -> Model:
    dtype = jnp.dtype(cfg.param_dtype)
    b = ParamBuilder(rng, dtype, abstract=abstract)
    b.add("embed", (cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=0.02)
    if not cfg.tie_embeddings:
        b.add("lm_head", (cfg.d_model, cfg.vocab), ("embed", "vocab"))
    make_norm(b, "final_norm", cfg.d_model, cfg.norm)

    # modality frontends (stubs: a projection from precomputed embeddings)
    if cfg.frontend == "vision":
        b.add("vis_proj1", (1024, cfg.d_model), (None, "embed"))
        b.add("vis_proj2", (cfg.d_model, cfg.d_model), ("embed", "embed"))
    elif cfg.frontend == "audio":
        b.add("aud_proj", (128, cfg.d_model), (None, "embed"))
        if cfg.enc_seq:
            b.add("enc_pos", (cfg.enc_seq, cfg.d_model), (None, "embed"),
                  scale=0.02)
    if cfg.norm == "layernorm" and max_positions:
        b.add("dec_pos", (max_positions, cfg.d_model), (None, "embed"),
              scale=0.02)

    # encoder stack (whisper)
    for i in range(cfg.n_enc_layers):
        make_block(b, cfg, ("attn_bidir", "dense"), f"enc.{i}")
    if cfg.n_enc_layers:
        make_norm(b, "enc_norm", cfg.d_model, cfg.norm)

    # decoder prefix (unrolled)
    cross = cfg.is_encdec
    for i, spec in enumerate(cfg.prefix_layers):
        make_block(b, cfg, spec, f"prefix.{i}", cross=cross)

    # periodic pattern (params stacked over periods for lax.scan)
    if cfg.n_periods > 0:
        def init_slots(key):
            pb = ParamBuilder(key, dtype, abstract=abstract)
            for s_i, spec in enumerate(cfg.pattern):
                make_block(pb, cfg, spec, f"slot{s_i}", cross=cross)
            return pb

        if abstract:
            pb = init_slots(None)
            stacked = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((cfg.n_periods,) + s.shape,
                                               s.dtype), pb.params)
        else:
            pb = init_slots(jax.random.key(0))  # for the spec tree only
            keys = jax.random.split(b._next(), cfg.n_periods)
            stacked = jax.vmap(lambda k: init_slots(k).params)(keys)
        b.params["pattern"] = stacked
        b.specs["pattern"] = {k: (None,) + v for k, v in pb.specs.items()}

    if cfg.mtp:
        make_norm(b, "mtp.norm_h", cfg.d_model, cfg.norm)
        make_norm(b, "mtp.norm_e", cfg.d_model, cfg.norm)
        b.add("mtp.proj", (2 * cfg.d_model, cfg.d_model), (None, "embed"))
        make_block(b, cfg, ("attn", "dense"), "mtp.block")
    return Model(b.params, b.specs)


def abstract_params(cfg: ModelConfig, max_positions: int = 0) -> Model:
    """Shape/dtype-only params (no allocation) for lowering/dry-run."""
    return init_params(None, cfg, max_positions, abstract=True)


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _embed_inputs(params: Dict, cfg: ModelConfig, batch: Dict) -> jnp.ndarray:
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.frontend == "vision" and "patches" in batch:
        p = batch["patches"]
        p = jax.nn.gelu(p @ params["vis_proj1"]) @ params["vis_proj2"]
        x = jnp.concatenate([p.astype(x.dtype), x], axis=1)
    if cfg.norm == "layernorm" and "dec_pos" in params:
        s = x.shape[1]
        pos0 = batch.get("pos_offset", 0)
        x = x + jax.lax.dynamic_slice_in_dim(
            params["dec_pos"], pos0, s, axis=0)[None]
    return shard(x, "batch", "seq", "embed")


def _encode(params: Dict, cfg: ModelConfig, frames: jnp.ndarray) -> jnp.ndarray:
    """Whisper-style encoder over stub frame embeddings (B, T, 128)."""
    x = frames @ params["aud_proj"]
    if "enc_pos" in params:
        x = x + params["enc_pos"][None, : x.shape[1]]
    pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
    for i in range(cfg.n_enc_layers):
        x, _, _ = block_forward(params, cfg, ("attn_bidir", "dense"),
                                f"enc.{i}", x, pos)
    return apply_norm(params, "enc_norm", x, cfg.norm)


def _run_stack(
    params: Dict, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray,
    *, caches=None, cache_pos=None, enc_out=None, decode=False,
) -> Tuple[jnp.ndarray, Any, Dict]:
    """Prefix (unrolled) + pattern (scanned over periods)."""
    aux_all: Dict = {}
    new_prefix = []
    remat = cfg.remat != "none"

    def prefix_body(x, i, spec, cache):
        return block_forward(params, cfg, spec, f"prefix.{i}", x, positions,
                             cache=cache, cache_pos=cache_pos,
                             enc_out=enc_out, decode=decode)

    for i, spec in enumerate(cfg.prefix_layers):
        cache_i = caches["prefix"][i] if caches is not None else None
        fn = jax.checkpoint(prefix_body, static_argnums=(1, 2)) if remat \
            else prefix_body
        x, nc, aux = fn(x, i, spec, cache_i)
        new_prefix.append(nc)

    new_pattern = None
    if cfg.n_periods > 0:
        pat = params["pattern"]

        def period_body(x, inp):
            pparams, pcache = inp
            ncs = {}
            for s_i, spec in enumerate(cfg.pattern):
                c = pcache[f"slot{s_i}"] if pcache is not None else None
                x, nc, _aux = block_forward(
                    pparams, cfg, spec, f"slot{s_i}", x, positions,
                    cache=c, cache_pos=cache_pos, enc_out=enc_out,
                    decode=decode)
                ncs[f"slot{s_i}"] = nc if nc is not None else 0
            return x, ncs

        body = jax.checkpoint(period_body) if remat else period_body
        pcaches = caches["pattern"] if caches is not None else None
        u = min(_unroll(), cfg.n_periods)
        if pcaches is None:
            x, _ = jax.lax.scan(
                lambda carry, p: body(carry, (p, None)), x, pat, unroll=u)
        else:
            x, new_pattern = jax.lax.scan(
                lambda carry, inp: body(carry, inp), x, (pat, pcaches),
                unroll=u)

    new_caches = None
    if caches is not None:
        new_caches = {"prefix": new_prefix, "pattern": new_pattern}
    return x, new_caches, aux_all


def forward_train(params: Dict, cfg: ModelConfig, batch: Dict
                  ) -> Tuple[jnp.ndarray, Dict]:
    """Returns (mean loss, metrics). batch: tokens (B,S), labels (B,S),
    optional patches/frames; labels == -100 are masked."""
    x = _embed_inputs(params, cfg, batch)
    bsz, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (bsz, s))
    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(params, cfg, batch["frames"])
    x, _, aux = _run_stack(params, cfg, x, positions, enc_out=enc_out)
    x = apply_norm(params, "final_norm", x, cfg.norm)

    labels = batch["labels"]
    if cfg.frontend == "vision" and "patches" in batch:
        # patch positions carry no next-token loss
        pad = jnp.full((bsz, x.shape[1] - labels.shape[1]), -100, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)

    loss, metrics = _lm_loss(params, cfg, x, labels)
    if cfg.mtp and "tokens" in batch:
        loss = loss + 0.3 * _mtp_loss(params, cfg, x, batch, positions)
        metrics["mtp"] = True
    metrics.update({k: v for k, v in aux.items()})
    return loss, metrics


def _logits(params: Dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    return shard(logits, "batch", "seq", "vocab")


def _lm_loss(params, cfg, x, labels) -> Tuple[jnp.ndarray, Dict]:
    logits = _logits(params, cfg, x).astype(jnp.float32)
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(mask.sum(), 1)
    loss = nll.sum() / denom
    return loss, {"loss": loss, "tokens": denom}


def _mtp_loss(params, cfg, x, batch, positions) -> jnp.ndarray:
    """DeepSeek-V3 multi-token prediction (depth 1): predict t+2."""
    tokens = batch["tokens"]
    emb_next = jnp.take(params["embed"], jnp.roll(tokens, -1, axis=1), axis=0)
    if x.shape[1] != tokens.shape[1]:  # VLM: only text tail carries MTP
        x = x[:, -tokens.shape[1]:]
        positions = positions[:, -tokens.shape[1]:]
    h = jnp.concatenate(
        [apply_norm(params, "mtp.norm_h", x, cfg.norm),
         apply_norm(params, "mtp.norm_e", emb_next.astype(x.dtype), cfg.norm)],
        axis=-1) @ params["mtp.proj"]
    h, _, _ = block_forward(params, cfg, ("attn", "dense"), "mtp.block", h,
                            positions)
    labels2 = jnp.roll(batch["labels"], -2, axis=1).at[:, -2:].set(-100)
    loss, _ = _lm_loss(params, cfg, h, labels2)
    return loss


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    def one(spec):
        mixer, _ = spec
        if mixer == "attn":
            return init_kv_cache(batch, max_len, cfg.n_kv_heads, cfg.head_dim,
                                 cfg.kv_cache_dtype)
        if mixer == "mla":
            return init_mla_cache(batch, max_len, cfg)
        if mixer == "ssm":
            s = cfg.ssm
            di = s.expand * cfg.d_model
            nh = di // s.head_dim
            return SSMCache(
                jnp.zeros((batch, s.d_conv - 1, di + 2 * s.d_state),
                          jnp.dtype(cfg.dtype)),
                jnp.zeros((batch, nh, s.d_state, s.head_dim), jnp.float32),
            )
        return None

    prefix = [one(s) for s in cfg.prefix_layers]
    pattern = None
    if cfg.n_periods > 0:
        pattern = {}
        for s_i, spec in enumerate(cfg.pattern):
            c = one(spec)
            pattern[f"slot{s_i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[None], (cfg.n_periods,) + a.shape).copy(), c)
    return {"prefix": prefix, "pattern": pattern}


def forward_prefill(params: Dict, cfg: ModelConfig, batch: Dict,
                    caches: Dict) -> Tuple[jnp.ndarray, Dict]:
    """Run the full prompt, fill caches; returns (last-position logits, caches)."""
    x = _embed_inputs(params, cfg, batch)
    bsz, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (bsz, s))
    enc_out = _encode(params, cfg, batch["frames"]) if cfg.is_encdec else None
    x, caches, _ = _run_stack(params, cfg, x, positions, caches=caches,
                              cache_pos=0, enc_out=enc_out)
    x = apply_norm(params, "final_norm", x, cfg.norm)
    logits = _logits(params, cfg, x[:, -1:])
    if enc_out is not None:
        caches = dict(caches, enc_out=enc_out)
    return logits, caches


def forward_decode(params: Dict, cfg: ModelConfig, token: jnp.ndarray,
                   pos, caches: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One decode step. token (B, 1) int32; pos scalar int32 position."""
    batch = {"tokens": token, "pos_offset": pos}
    x = jnp.take(params["embed"], token, axis=0)
    if cfg.norm == "layernorm" and "dec_pos" in params:
        x = x + jax.lax.dynamic_slice_in_dim(params["dec_pos"], pos, 1,
                                             axis=0)[None]
    x = shard(x, "batch", None, "embed")
    bsz = x.shape[0]
    positions = jnp.full((bsz, 1), pos, jnp.int32)
    enc_out = caches.get("enc_out") if isinstance(caches, dict) else None
    run_caches = {"prefix": caches["prefix"], "pattern": caches["pattern"]}
    x, new_caches, _ = _run_stack(params, cfg, x, positions, caches=run_caches,
                                  cache_pos=pos, enc_out=enc_out, decode=True)
    x = apply_norm(params, "final_norm", x, cfg.norm)
    logits = _logits(params, cfg, x)
    if enc_out is not None:
        new_caches = dict(new_caches, enc_out=enc_out)
    return logits, new_caches
