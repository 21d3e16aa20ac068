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
import re
from typing import NamedTuple

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


class Compiled(NamedTuple):
    coeffs: object      # the compiled entropy stage
    pixels: object      # the compiled pixel stage
    program: object     # their DecodeProgram


def compile_decode_programs(blobs, chunk_bits, sharding):
    """Lower and compile the jnp backend's two programs (entropy stage
    ``coeffs_fn`` and pixel stage ``pixels_fn``) for ``blobs``' bucket on
    the device behind ``sharding``. Returns both compiled programs and the
    :class:`DecodeProgram` they came from."""
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
    return Compiled(coeffs, pixels, prog)


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
    for compiled in (programs_1080p.coeffs, programs_1080p.pixels):
        mem = compiled.memory_analysis()
        need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes)
        assert 0 < need < HBM_BYTES


def test_entropy_program_names_both_phases_on_v5e(programs_1080p):
    """The named scopes of the entropy stage survive the TPU compiler: its
    entry-level instructions map to the sync and the write phase."""
    from repro.core import api
    phases = api.hlo_phases(programs_1080p.coeffs.as_text())
    assert set(phases.values()) == {api.SYNC_PHASE, api.WRITE_PHASE}


def _computations(hlo_text):
    """``{computation name: its instruction lines}`` of HLO text."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line.strip())
    return comps


def _reached(comps, root):
    """The computations ``root`` runs, nested loops' bodies left out."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            if " while(" in line:
                continue
            todo += re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", line)
    return seen


def test_symbol_step_loops_keep_only_the_lut_gather(programs_1080p):
    """Each symbol-step loop of the 1080p entropy program (the innermost
    loops: the initial pass, the rounds' step and the write pass) holds
    one gather, and it reads the LUTs: the words and the LUT row are
    staged and selected without one."""
    assert programs_1080p.program.step_staged
    luts = programs_1080p.program.shape.n_luts
    comps = _computations(programs_1080p.coeffs.as_text())
    bodies = [re.search(r"body=%([\w.\-]+)", line).group(1)
              for lines in comps.values() for line in lines
              if " while(" in line]
    steps = [b for b in bodies
             if not any(" while(" in line for c in _reached(comps, b)
                        for line in comps[c])]
    assert len(steps) == 3
    for body in steps:
        reached = _reached(comps, body)
        gathers = [(c, line) for c in reached for line in comps[c]
                   if re.search(r"[ )]gather\(", line.split(" = ", 1)[-1])]
        assert len(gathers) == 1, gathers
        comp, line = gathers[0]
        table = re.search(r"gather\(%([\w.\-]+)", line).group(1)
        (decl,) = [x for x in comps[comp] if x.startswith(f"%{table} = ")]
        assert f"s32[{luts},65536]" in decl


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


def test_mixed_crop_program_compiles_at_ilsvrc_size(one_chip):
    """The crop stage of a mixed batch (4:2:0, 4:4:4 and gray frames),
    lowered at the ILSVRC-2012 training cell's size: 128 images, 999,015
    data units of capacity, 224x224 crops. It compiles for v5e within a
    quarter of the chip's memory, and its one gather, the taps', lies in
    the crop scope."""
    from conftest import synth_image
    from repro.core import ParallelDecoder, api
    from repro.core.bitstream import LAYOUT_COLS
    from repro.jpeg import codec_ref as cr
    blobs = [cr.encode_baseline(img, quality=90, subsampling=s).jpeg_bytes
             for img, s in ((synth_image(37, 50, seed=1), "4:2:0"),
                            (synth_image(50, 33, seed=2), "4:4:4"),
                            (synth_image(30, 40, seed=3)[..., 0], "gray"))]
    dec = ParallelDecoder.from_bytes(blobs, chunk_bits=1024)
    assert not dec.plan.uniform
    units, images = 999_015, 128
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,  # noqa: E731
                                                    sharding=one_chip)
    cropdev = {"m_matrices": sds(dec._pixdev["m_matrices"].shape, jnp.float32),
               "unit_mrow": sds((units,), jnp.int32),
               "layout": sds((images, LAYOUT_COLS), jnp.int32)}
    compiled = dec.program.crop_fn.lower(
        cropdev, sds((units, 64), jnp.int32), sds((images, 4), jnp.int32),
        api.CROP_SIZE, None).compile()
    mem = compiled.memory_analysis()
    need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert 0 < need < HBM_BYTES // 4
    text = compiled.as_text()
    assert api.CROP_PHASE in set(api.hlo_phases(text, (api.CROP_PHASE,)).values())
    gathers = [line for line in text.splitlines()
               if re.search(r"[ )]gather\(", line.split(" = ", 1)[-1])]
    assert len(gathers) == 1 and api.CROP_PHASE in gathers[0]
