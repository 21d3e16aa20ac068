"""Shared kernel-backend selection for every Pallas wrapper.

Three orthogonal knobs, used across ``kernels/`` and threaded through the
decoder API (``core/api.py``):

* ``backend`` — which implementation family executes the hot path:
  ``"jnp"`` (pure-JAX reference decoder) or ``"pallas"`` (the kernels in
  this package). Unknown names raise immediately; a silent fallback is
  exactly the bug this module exists to prevent (``use_kernels=True``
  historically swapped only the IDCT and dropped the Huffman kernel on
  the floor).

* ``fuse`` — how aggressively the Pallas path fuses decode stages
  (``kernels/fused``): ``"none"`` keeps the historical one-kernel-per-
  stage layout, ``"post"`` fuses the post-entropy chain (dequant +
  de-zigzag + IDCT + chroma upsample + color convert) into a single
  launch, and ``"full"`` additionally collapses the write pass's
  ``(C, s_max)`` stream + bulk scatter into an in-kernel coefficient
  store wherever that is provably race-free. The jnp backend has no
  kernels to fuse, so it only accepts ``"none"``. Resolution order:
  explicit argument > ``REPRO_PALLAS_FUSE`` env var > ``"post"`` (the
  autotuned default for the Pallas backend).

* ``interpret`` — whether a Pallas call runs compiled (Mosaic on TPU,
  Triton on GPU) or through the interpreter. The wrappers used to
  hardcode ``interpret=True``, which pinned every deployment to the
  interpreter: compiled Pallas never ran off-CPU. Resolution order:

    1. an explicit ``interpret=`` argument (tests force interpret mode),
    2. the ``REPRO_PALLAS_INTERPRET`` env var (``"1"``/``"0"``); ``"1"``
       on an accelerator raises, so a stray variable can never run the
       kernels in the interpreter on a chip,
    3. platform default: interpret on CPU (the only backend the
       interpreter-free path cannot target), compiled on TPU/GPU.
"""
from __future__ import annotations

import functools
import os
import warnings
from typing import Optional

import jax

BACKENDS = ("jnp", "pallas")
FUSE_MODES = ("none", "post", "full")

INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"
FUSE_ENV = "REPRO_PALLAS_FUSE"


def check_backend(backend: str) -> str:
    """Validate a decode-backend name; raise (never coerce) on junk."""
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown decode backend {backend!r}; expected one of {BACKENDS}"
        )
    return backend


def resolve_backend(backend: Optional[str], use_kernels: bool = False) -> str:
    """Map the (backend, legacy use_kernels) pair to a validated backend."""
    if use_kernels:
        # the legacy boolean predates both the backend knob and the fuse
        # knob; it can only ever say "pallas, however the defaults fall"
        warnings.warn(
            "use_kernels= is deprecated; pass backend=\"pallas\" (and "
            "optionally fuse=\"none\"|\"post\"|\"full\") instead",
            DeprecationWarning, stacklevel=3)
    if backend is None:
        return "pallas" if use_kernels else "jnp"
    backend = check_backend(backend)
    if use_kernels and backend != "pallas":
        raise ValueError(
            f"conflicting backend selection: use_kernels=True with "
            f"backend={backend!r} would silently drop the kernels; pass "
            f"one or the other"
        )
    return backend


def check_fuse(fuse: str, backend: str = "pallas") -> str:
    """Validate a fuse-mode name against a backend; raise on junk."""
    if fuse not in FUSE_MODES:
        raise ValueError(
            f"unknown fuse mode {fuse!r}; expected one of {FUSE_MODES}"
        )
    if backend != "pallas" and fuse != "none":
        raise ValueError(
            f"fuse={fuse!r} requires backend=\"pallas\"; the {backend!r} "
            f"backend has no kernels to fuse (use fuse=\"none\")"
        )
    return fuse


def resolve_fuse(fuse: Optional[str], backend: str) -> str:
    """Resolve the effective fuse mode: argument > env > per-backend default.

    The Pallas default is ``"post"`` — the post-entropy megakernel is
    bit-identical to the unfused chain and strictly cheaper in launches
    and inter-stage HBM traffic, so it is the autotuner's standing pick;
    ``"full"`` stays opt-in because its in-kernel store only engages
    off-mesh (it falls back to the stream form elsewhere).
    """
    if fuse is None:
        if backend != "pallas":
            return "none"
        fuse = os.environ.get(FUSE_ENV) or "post"
    return check_fuse(fuse, backend)


def default_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve the effective ``interpret`` flag for a Pallas call."""
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get(INTERPRET_ENV)
    if env is not None:
        if env not in ("0", "1"):
            raise ValueError(
                f"{INTERPRET_ENV} must be '0' or '1', got {env!r}"
            )
        platform = jax.default_backend()
        if env == "1" and platform != "cpu":
            raise ValueError(
                f"{INTERPRET_ENV}=1 would run the Pallas kernels in the "
                f"interpreter on {platform!r}; unset it on an accelerator"
            )
        return env == "1"
    return jax.default_backend() == "cpu"


class KernelRefusedError(RuntimeError):
    """The target chip's compiler refuses a kernel the Pallas backend
    needs; raised instead of falling back to jnp or the interpreter."""


@functools.lru_cache(maxsize=None)
def _huffman_refusal(device) -> Optional[str]:
    from .huffman.huffman import compile_refusal
    return compile_refusal(device)


def check_pallas_compiles(device) -> None:
    """Raise :class:`KernelRefusedError` when ``device``'s compiler refuses
    the Huffman symbol-step kernel that every Pallas decode runs."""
    refusal = _huffman_refusal(device)
    if refusal is not None:
        raise KernelRefusedError(
            f"backend=\"pallas\" cannot run on {device.device_kind}: Mosaic "
            f"refuses the Huffman symbol step's per-lane word gather and "
            f"flat LUT gather (kernels/huffman/huffman.py): {refusal}. "
            f"Use backend=\"jnp\".")
