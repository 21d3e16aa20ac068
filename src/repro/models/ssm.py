"""Mamba-2 SSD (state-space duality) mixer with chunked scan + decode cache.

Chunked form (Mamba-2 paper §6): within a chunk the output is a masked
"attention" G = (C B^T) ⊙ L; across chunks a size-(H, P, N) state is carried
by an exponential recurrence — O(S) work, constant state, which is what makes
the 500k-token cells feasible (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import shard
from .config import ModelConfig
from .layers import ParamBuilder, rmsnorm


class SSMCache(NamedTuple):
    conv: jnp.ndarray    # (B, d_conv-1, d_conv_channels) rolling conv input
    state: jnp.ndarray   # (B, H, P, N) SSD state


def make_ssd(b: ParamBuilder, cfg: ModelConfig, name: str):
    d = cfg.d_model
    s = cfg.ssm
    di = s.expand * d
    nh = di // s.head_dim
    conv_ch = di + 2 * s.d_state
    b.add(f"{name}.w_in", (d, 2 * di + 2 * s.d_state + nh), ("embed", "mlp"))
    b.add(f"{name}.conv_w", (s.d_conv, conv_ch), (None, "mlp"))
    b.add(f"{name}.conv_b", (conv_ch,), ("mlp",), init="zeros")
    b.add(f"{name}.a_log", (nh,), ("heads",), init="zeros")
    b.add(f"{name}.dt_bias", (nh,), ("heads",), init="zeros")
    b.add(f"{name}.d_skip", (nh,), ("heads",), init="zeros")
    b.add(f"{name}.out_norm", (di,), ("mlp",), init="zeros")
    b.add(f"{name}.w_out", (di, d), ("mlp", "embed"))


def _split_in(cfg: ModelConfig, proj: jnp.ndarray):
    s = cfg.ssm
    di = s.expand * cfg.d_model
    nh = di // s.head_dim
    z, xbc_dt = jnp.split(proj, [di], axis=-1)
    xbc, dt = jnp.split(xbc_dt, [di + 2 * s.d_state], axis=-1)
    return z, xbc, dt, di, nh


def _ssd_chunked(xh, dt, a, bmat, cmat, chunk: int):
    """Chunked SSD.

    xh (B,S,H,P)  dt (B,S,H)  a (H,) negative decay
    bmat/cmat (B,S,N) single group. Returns (B,S,H,P) and final state.
    """
    bsz, s, h, p = xh.shape
    n = bmat.shape[-1]
    pad = (-s) % chunk
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))
    nc = xh.shape[1] // chunk
    xc = xh.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h)
    bc = bmat.reshape(bsz, nc, chunk, n)
    cc = cmat.reshape(bsz, nc, chunk, n)

    da = dtc * a[None, None, None, :]              # (B,nc,Q,H) negative
    cum = jnp.cumsum(da, axis=2)                   # within-chunk cumulative

    # intra-chunk: G[i,j] = C_i . B_j * exp(cum_i - cum_j) * dt_j  (i >= j)
    li = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # (B,nc,Q,Q,H)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(tri[None, None, :, :, None], jnp.exp(li), 0.0)
    gb = jnp.einsum("bcin,bcjn->bcij", cc, bc)              # (B,nc,Q,Q)
    w = gb[..., None] * decay * dtc[:, :, None, :, :]       # (B,nc,Q,Q,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", w, xc)

    # chunk summary states: S_c = sum_j exp(cum_Q - cum_j) dt_j B_j x_j^T
    decay_out = jnp.exp(cum[:, :, -1:, :] - cum)            # (B,nc,Q,H)
    sc = jnp.einsum("bcqh,bcqn,bcqhp->bchnp",
                    decay_out * dtc, bc, xc)                # (B,nc,H,N,P)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                 # (B,nc,H)

    def step(hstate, inp):
        s_c, dec = inp                                       # (B,H,N,P),(B,H)
        y_state = hstate                                     # entering state
        hstate = hstate * dec[..., None, None] + s_c
        return hstate, y_state

    h0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    hN, h_in = jax.lax.scan(
        step,
        h0,
        (sc.transpose(1, 0, 2, 3, 4).astype(jnp.float32),
         chunk_decay.transpose(1, 0, 2)),
    )
    h_in = h_in.transpose(1, 0, 2, 3, 4)                     # (B,nc,H,N,P)

    # inter-chunk contribution: y_i += C_i exp(cum_i) H_in
    y_inter = jnp.einsum("bcqn,bcqh,bchnp->bcqhp",
                         cc, jnp.exp(cum), h_in.astype(cc.dtype))
    y = (y_intra + y_inter).reshape(bsz, nc * chunk, h, p)[:, :s]
    return y, hN


def ssd_forward(
    params: Dict, cfg: ModelConfig, name: str, x: jnp.ndarray,
    *, cache: Optional[SSMCache] = None,
) -> Tuple[jnp.ndarray, Optional[SSMCache]]:
    """Full-sequence (train/prefill) forward. Returns output and final cache."""
    s_cfg = cfg.ssm
    bsz, s, _ = x.shape
    proj = jnp.einsum("bsd,de->bse", x, params[f"{name}.w_in"])
    z, xbc, dt, di, nh = _split_in(cfg, proj)

    # causal depthwise conv over (x, B, C) channels — accumulated in f32
    # and rounded once, bit-matching ssd_decode_step's f32 sum-of-products
    w = params[f"{name}.conv_w"]                  # (K, C)
    k = s_cfg.d_conv
    pad_in = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(
        pad_in[:, i : i + s, :].astype(jnp.float32)
        * w[i].astype(jnp.float32)[None, None, :] for i in range(k)
    ) + params[f"{name}.conv_b"].astype(jnp.float32)[None, None, :]
    conv = jax.nn.silu(conv).astype(xbc.dtype)

    xh, bmat, cmat = jnp.split(conv, [di, di + s_cfg.d_state], axis=-1)
    xh = xh.reshape(bsz, s, nh, s_cfg.head_dim)
    xh = shard(xh, "batch", "seq", "heads", None)
    a = -jnp.exp(params[f"{name}.a_log"].astype(jnp.float32))
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params[f"{name}.dt_bias"].astype(jnp.float32))

    # the scan runs in f32 and rounds once, as ssd_decode_step does: a
    # bf16 chunked scan drifts from the f32 recurrence the decode step
    # continues from
    f32 = jnp.float32
    y, h_final = _ssd_chunked(xh.astype(f32), dt, a, bmat.astype(f32),
                              cmat.astype(f32), s_cfg.chunk)
    y = y + xh.astype(f32) * params[f"{name}.d_skip"].astype(f32)[
        None, None, :, None]
    y = y.reshape(bsz, s, di)
    y = rmsnorm(y.astype(x.dtype) * jax.nn.silu(z), params[f"{name}.out_norm"])
    out = jnp.einsum("bse,ed->bsd", y.astype(x.dtype), params[f"{name}.w_out"])

    new_cache = None
    if cache is not None:
        conv_tail = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))[:, -(k - 1):, :]
        new_cache = SSMCache(conv_tail.astype(cache.conv.dtype),
                             h_final.astype(cache.state.dtype))
    return shard(out, "batch", "seq", "embed"), new_cache


def ssd_decode_step(
    params: Dict, cfg: ModelConfig, name: str, x: jnp.ndarray,
    cache: SSMCache,
) -> Tuple[jnp.ndarray, SSMCache]:
    """Single-token recurrent step. x (B, 1, d)."""
    s_cfg = cfg.ssm
    bsz = x.shape[0]
    proj = jnp.einsum("bsd,de->bse", x, params[f"{name}.w_in"])
    z, xbc, dt, di, nh = _split_in(cfg, proj)
    k = s_cfg.d_conv
    w = params[f"{name}.conv_w"]
    window = jnp.concatenate([cache.conv, xbc], axis=1)      # (B, k, C)
    conv = sum(
        window[:, i, :].astype(jnp.float32) * w[i].astype(jnp.float32)[None, :]
        for i in range(k)
    ) + params[f"{name}.conv_b"].astype(jnp.float32)[None, :]
    conv = jax.nn.silu(conv).astype(xbc.dtype)[:, None, :]
    xh, bmat, cmat = jnp.split(conv, [di, di + s_cfg.d_state], axis=-1)
    xh = xh.reshape(bsz, nh, s_cfg.head_dim)                 # (B,H,P)
    bmat = bmat[:, 0]                                        # (B,N)
    cmat = cmat[:, 0]
    a = -jnp.exp(params[f"{name}.a_log"].astype(jnp.float32))
    dt_ = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                          + params[f"{name}.dt_bias"].astype(jnp.float32))
    dec = jnp.exp(dt_ * a[None, :])                          # (B,H)
    state = cache.state.astype(jnp.float32)
    state = state * dec[..., None, None] + jnp.einsum(
        "bh,bn,bhp->bhnp", dt_, bmat.astype(jnp.float32),
        xh.astype(jnp.float32))
    y = jnp.einsum("bn,bhnp->bhp", cmat.astype(jnp.float32), state)
    y = y + xh.astype(jnp.float32) * params[f"{name}.d_skip"].astype(
        jnp.float32)[None, :, None]
    y = y.reshape(bsz, 1, di)
    y = rmsnorm(y.astype(x.dtype) * jax.nn.silu(z), params[f"{name}.out_norm"])
    out = jnp.einsum("bse,ed->bsd", y.astype(x.dtype), params[f"{name}.w_out"])
    new_cache = SSMCache(window[:, 1:, :].astype(cache.conv.dtype),
                         state.astype(cache.state.dtype))
    return out, new_cache
