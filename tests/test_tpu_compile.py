"""Compile the decoder's chip programs for a described TPU v5e.

Nothing runs: the TPU compiler that ships with JAX compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what
the chip's compiler would refuse. The
topology is made in a module-scoped fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library. The persistent compilation cache
is off around these compiles: an entry written here cannot be read back
without a chip.
"""
import dataclasses
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 2**30   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def compile_decode_programs(blobs, chunk_bits, sharding):
    """Lower and compile the jnp backend's two programs (entropy stage
    ``coeffs_fn`` and pixel stage ``pixels_fn``) for ``blobs``' bucket on
    the device behind ``sharding``. Returns the two compiled programs."""
    from repro.core import ParallelDecoder
    from repro.dist import sharding as S

    dec = ParallelDecoder.from_bytes(blobs, chunk_bits=chunk_bits,
                                     sync="jacobi")
    token = S.trace_token()
    prog = dec.program
    coeffs = prog.coeffs_fn.lower(
        _sds(dec.data.words, sharding), _sds(dec._dev_rest, sharding),
        token).compile()
    units = jax.ShapeDtypeStruct((dec.plan.total_units, 64), jnp.int32,
                                 sharding=sharding)
    pixels = prog.pixels_fn.lower(
        _sds(dec._pixdev, sharding), _sds(dec._pix_layout, sharding), units,
        token).compile()
    return coeffs, pixels


@pytest.fixture(scope="module")
def programs_1080p(one_chip):
    """The main path's two programs for one full-size ``newyork`` frame
    (1920x1080, q95, 1024-bit subsequences), compiled for v5e."""
    from repro.jpeg.encoder import PAPER_DATASETS, build_dataset
    spec = dataclasses.replace(PAPER_DATASETS["newyork"], n_images=1)
    blobs = build_dataset(spec, seed=0).jpeg_bytes
    return compile_decode_programs(blobs, spec.subsequence_bits, one_chip)


def test_jnp_decode_programs_compile_at_1080p(programs_1080p):
    """Both programs compile for v5e and fit its memory."""
    for compiled in programs_1080p:
        mem = compiled.memory_analysis()
        need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes)
        assert 0 < need < HBM_BYTES


def test_entropy_program_names_both_phases_on_v5e(programs_1080p):
    """The named scopes of the entropy stage survive the TPU compiler: its
    entry-level instructions map to the sync and the write phase."""
    from repro.core import api
    phases = api.hlo_phases(programs_1080p[0].as_text())
    assert set(phases.values()) == {api.SYNC_PHASE, api.WRITE_PHASE}


def _idct(one):
    from repro.kernels.idct.idct import fused_idct
    u = 195_840  # 4 x 1080p at 4:2:0
    return jax.jit(lambda c, m, r: fused_idct(c, m, r, interpret=False)).lower(
        jax.ShapeDtypeStruct((u, 64), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((2, 64, 64), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((u,), jnp.int32, sharding=one))


def _color(one):
    from repro.kernels.color.color import upsample_color

    def plane(h, w):
        return jax.ShapeDtypeStruct((4, h, w), jnp.float32, sharding=one)
    return jax.jit(lambda y, cb, cr: upsample_color(
        y, cb, cr, fh=2, fv=2, interpret=False)).lower(
        plane(1080, 1920), plane(540, 960), plane(540, 960))


@pytest.mark.parametrize("kernel", ["idct", "color"])
def test_repaired_kernel_compiles_at_real_tiles(one_chip, kernel):
    compiled = {"idct": _idct, "color": _color}[kernel](one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_huffman_kernel_still_refused(topo):
    """The check ``ParallelDecoder(backend="pallas")`` runs on a TPU. This
    fails the day Mosaic compiles the Huffman symbol step: then lift the
    guard in ``core/api.py`` and ``kernels/backend.py``."""
    from repro.kernels import backend as KB
    with pytest.raises(KB.KernelRefusedError, match="gather"):
        KB.check_pallas_compiles(topo.devices[0])
