"""Mixed-geometry batches through the normal path, to coefficients and to
fixed-size crops, against the sequential oracle (``jpeg/codec_ref.py``):
coefficients exact, crops within 1 of ``codec_ref.crop_resize`` (float64),
one pixel program per capacity bucket, and the same crops on a mesh."""
import numpy as np
import pytest

from conftest import synth_image
from _multiproc import run_sub
from repro.core import ParallelDecoder, decode_batch
from repro.core.bitstream import plan_shape
from repro.jpeg import codec_ref as cr

# (height, width, sampling): none a multiple of its MCU, so every frame
# has partial MCUs at its right and bottom edges
FRAMES = {"4:2:0": (37, 53, "4:2:0"), "4:2:2": (40, 33, "4:2:2"),
          "4:4:4": (27, 45, "4:4:4"), "gray": (29, 41, "gray")}


def _blob(height, width, sampling, seed, quality=85):
    img = synth_image(height, width, seed=seed)
    if sampling == "gray":
        img = img[..., 0]
    return cr.encode_baseline(img, quality=quality,
                              subsampling=sampling).jpeg_bytes


def _batch(kinds, seed=0):
    return [_blob(*FRAMES[k], seed=seed + i) for i, k in enumerate(kinds)]


def _oracle_coeffs(blobs):
    return np.concatenate([cr.undiff_dc(p := cr.parse_jpeg(b),
                                        cr.decode_coefficients(p))
                           for b in blobs])


BATCHES = {
    "all-four": ("4:2:0", "4:2:2", "4:4:4", "gray"),
    "gray-first": ("gray", "4:2:0", "gray", "4:4:4"),
    "chroma-only": ("4:4:4", "4:2:2", "4:2:0"),
}


@pytest.mark.parametrize("kinds", list(BATCHES.values()), ids=list(BATCHES))
def test_mixed_batch_coefficients_equal_the_oracle(kinds):
    blobs = _batch(kinds)
    out = decode_batch(blobs, chunk_bits=256, emit="coeffs")
    assert not out.plan.uniform
    assert np.array_equal(np.asarray(out.coeffs), _oracle_coeffs(blobs))


def _boxes(case, frames):
    """One (top, left, height, width) box per frame, of the named kind."""
    out = []
    for k, f in enumerate(frames):
        h, w = f.shape[:2]
        out.append({
            "full-frame": (0, 0, h, w),
            # the box's far edges on the frame's partial MCUs
            "edge-touching": (h - h // 2, w - w // 3 - 1, h // 2, w // 3 + 1),
            "one-pixel": (h - 1 - k, k, 1, 1),
            # a box smaller than the output: every output reads 2x2 taps
            "upscaled": (2, 3, 7, 5),
        }[case])
    return np.array(out, np.int32)


@pytest.mark.parametrize("case", ["full-frame", "edge-touching", "one-pixel",
                                  "upscaled"])
def test_crops_within_one_of_the_oracle(case):
    blobs = _batch(BATCHES["all-four"], seed=10)
    frames = [cr.decode_baseline(b) for b in blobs]
    boxes = _boxes(case, frames)
    out = decode_batch(blobs, chunk_bits=256, emit="crop", crops=boxes,
                       out_size=(12, 20))
    got = np.asarray(out.crop)
    assert got.shape == (len(blobs), 12, 20, 3) and got.dtype == np.uint8
    assert np.array_equal(np.asarray(out.coeffs), _oracle_coeffs(blobs))
    for i, frame in enumerate(frames):
        ref = cr.crop_resize(frame, boxes[i], (12, 20))
        d = np.abs(got[i].astype(np.int16) - ref.astype(np.int16))
        assert d.max() <= 1, (i, d.max())


def test_uniform_batch_crop_is_the_resized_rgb():
    """On a uniform batch the crop stage reads the RGB the uniform pixel
    stage writes: a box resized to its own size is that RGB exactly."""
    blobs = [_blob(37, 53, "4:2:0", seed=s) for s in (1, 2)]
    dec = ParallelDecoder.from_bytes(blobs, chunk_bits=256)
    rgb = np.asarray(dec.decode(emit="rgb").rgb)
    boxes = np.array([[3, 4, 20, 30], [0, 0, 37, 53]], np.int32)
    crop = np.asarray(dec.decode(emit="crop", crops=boxes[:1].repeat(2, 0),
                                 out_size=(20, 30)).crop)
    assert np.array_equal(crop, rgb[:, 3:23, 4:34])
    whole = np.asarray(dec.decode(emit="crop", out_size=(37, 53)).crop)
    assert np.array_equal(whole, rgb)


@pytest.mark.parametrize("box", [(-1, 0, 4, 4), (0, 0, 0, 4), (30, 0, 8, 4),
                                 (0, 50, 4, 4)])
def test_a_box_outside_its_frame_is_refused(box):
    blobs = _batch(("4:2:0", "gray"))
    dec = ParallelDecoder.from_bytes(blobs, chunk_bits=256)
    with pytest.raises(ValueError, match="does not fit"):
        dec.decode(emit="crop", crops=[box, (0, 0, 4, 4)])


def test_a_stream_of_mixed_batches_compiles_the_pixel_program_once():
    """Three mixed batches of other frames and other boxes, one capacity
    bucket: the crop program traces once and serves all three."""
    from repro.core import clear_decode_programs
    clear_decode_programs()
    kinds = [BATCHES["all-four"], ("gray", "4:4:4", "4:2:0", "4:2:2"),
             ("4:2:2", "gray", "4:2:0", "4:4:4")]
    decs = [ParallelDecoder.from_bytes(_batch(k, seed=20 + 5 * i),
                                       chunk_bits=256)
            for i, k in enumerate(kinds)]
    assert len({d.shape for d in decs}) == 1
    assert decs[0].shape == plan_shape(decs[2].plan)
    prog = decs[0].program
    rng = np.random.default_rng(3)
    for dec in decs:
        boxes = [(int(rng.integers(0, g.height - 8)),
                  int(rng.integers(0, g.width - 8)), 8, 8)
                 for g in dec.plan.image_geometry]
        out = dec.decode(emit="crop", crops=boxes, out_size=(16, 16))
        assert out.crop.shape == (4, 16, 16, 3)
    assert prog.pixels_traces == 1 and prog.coeffs_traces == 1


def test_crops_under_decode_on_equal_one_device():
    out = run_sub("""
        import numpy as np, jax
        from test_crop import BATCHES, _batch, _boxes
        from repro.core import ParallelDecoder
        from repro.jpeg import codec_ref as cr
        blobs = _batch(BATCHES["all-four"] * 2, seed=30)
        boxes = _boxes("edge-touching", [cr.decode_baseline(b) for b in blobs])
        dec = ParallelDecoder.from_bytes(blobs, chunk_bits=256,
                                         balance="lpt", lanes=4)
        one = np.asarray(dec.decode(emit="crop", crops=boxes,
                                    out_size=(12, 20)).crop)
        mesh = jax.make_mesh((4,), ("data",))
        on = dec.decode_on(mesh, emit="crop", crops=boxes, out_size=(12, 20))
        assert np.array_equal(np.asarray(on.crop), one)
        print("SAME", one.shape)
    """, devices=4)
    assert "SAME (8, 12, 20, 3)" in out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_codec_ref_crop_agrees_with_the_benchmarks_reference(seed):
    """The program's oracle and the benchmark's own reference
    (``perfbench/harness/reference.crop_resize``) give the same crop of the
    same frame and box: colour and gray, down- and upscaled."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "perfbench"))
    from harness import reference as bench
    rng = np.random.default_rng(seed)
    for shape in [(41, 67, 3), (29, 33)]:
        img = rng.integers(0, 256, shape).astype(np.uint8)
        for _ in range(4):
            h = int(rng.integers(1, shape[0] + 1))
            w = int(rng.integers(1, shape[1] + 1))
            box = (int(rng.integers(0, shape[0] - h + 1)),
                   int(rng.integers(0, shape[1] - w + 1)), h, w)
            size = (int(rng.integers(1, 60)), int(rng.integers(1, 60)))
            assert np.array_equal(cr.crop_resize(img, box, size),
                                  bench.crop_resize(img, box + size))
