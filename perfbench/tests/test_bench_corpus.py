"""A configuration's corpus and answers as its file states them: the one-kind
form gives the bytes it always gave; a ``kinds`` corpus (mixed geometries,
a grayscale kind, restart markers) is drawn alike by any pool and decoded
alike by the reference, the program's oracle and the program; a crop answer
is compared through the stated resize; what the generator or the comparison
cannot honour is refused. CPU only."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import cell as C, check, corpus, reference, spec, work  # noqa: E402
from harness.window import Sample  # noqa: E402

# sha1 of frame 0 of each configuration's corpus, as the one-kind generator
# wrote it before corpora could state kinds
FIRST_FRAME_SHA1 = {
    "stata_480p": "5ae58a797ab9fc0643d9f023d06739d075bd85c9",
    "newyork_1080p": "2977844be0b13a3a51beb179d2f7f31c855759fd",
}

KINDS = {
    "name": "kinds_tiny", "n_images": 12,
    "corpus": {"seed": 10, "tables": "Annex K", "kinds": [
        {"share": 0.5, "quality": 90, "subsampling": "4:2:0", "restart_interval": 2,
         "geometries": [[48, 32, 0.5], [40, 24, 0.5]]},
        {"share": 0.25, "quality": 85, "subsampling": "gray", "restart_interval": 2,
         "geometries": [[32, 40, 1.0]]},
        {"share": 0.25, "quality": 95, "subsampling": "4:2:2", "restart_interval": 0,
         "geometries": [[24, 16, 1.0]]}]},
    "answers": {"resize": reference.RESIZE},
}


class SerialPool:
    """A pool that runs its tasks here, one after another."""

    def map(self, fn, tasks, chunksize=1):
        return [fn(t) for t in tasks]

    def map_async(self, fn, tasks, chunksize=1):
        out = self.map(fn, tasks)

        class Result:
            def get(self):
                return out
        return Result()


class RefusingPool:
    def map_async(self, fn, tasks, chunksize=1):
        raise AssertionError("the corpus started work")


@pytest.fixture(scope="module")
def blobs(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(corpus, "CACHE_DIR", str(tmp_path_factory.mktemp("corpus")))
        return corpus.start(KINDS, SerialPool()).get()


@pytest.mark.parametrize("name", sorted(FIRST_FRAME_SHA1))
def test_first_frame_of_a_one_kind_corpus_keeps_its_bytes(name):
    cfg = json.load(open(spec.config_path(name)))
    first = corpus._encode_frame((cfg["corpus"]["seed"], 0, corpus.corpus_spec(cfg)))
    assert hashlib.sha1(first).hexdigest() == FIRST_FRAME_SHA1[name]


def test_kinds_corpus_is_drawn_alike_whatever_the_pool(blobs, tmp_path, monkeypatch):
    monkeypatch.setattr(corpus, "CACHE_DIR", str(tmp_path))
    with C.make_pool(3) as pool:
        assert corpus.start(KINDS, pool).get() == blobs
    frames = [reference.parse(b) for b in blobs]
    assert {len(f.comps) for f in frames} == {1, 3}
    assert len({(f.width, f.height) for f in frames}) == 4
    assert {f.restart_interval for f in frames} == {0, 2}
    assert all(b"\xff\xd0" in f.scan for f in frames if f.restart_interval)
    # the kind and the geometry are drawn before the pixels from one generator
    kind, img = corpus.frame(10, 5, corpus.corpus_spec(KINDS))
    assert frames[5].width == img.shape[1] and len(frames[5].comps) == (
        1 if kind[2] == "gray" else 3)


def test_kinds_corpus_decodes_as_the_programs_oracle_and_the_program(blobs):
    from repro.core.api import ParallelDecoder
    from repro.jpeg import codec_ref as cr
    refs = []
    for blob in blobs:
        coeff, pix = reference.decode(blob)
        img = cr.parse_jpeg(blob)
        assert np.array_equal(coeff, cr.undiff_dc(img, cr.decode_coefficients(img)))
        assert np.array_equal(pix, cr.decode_baseline(blob))
        refs.append(coeff)
    out = ParallelDecoder.from_bytes(blobs, chunk_bits=256).coefficients()
    assert np.array_equal(np.asarray(out.coeffs), np.concatenate(refs))


@pytest.mark.parametrize("subsampling,restart", [("gray", 2), ("4:2:0", 3)])
def test_restarts_and_gray_match_the_programs_encoder(subsampling, restart):
    from repro.jpeg import codec_ref as cr
    img = corpus.synth_frame(np.random.default_rng(7), 56, 40, 0.4)
    if subsampling == "gray":
        img = corpus.gray(img)
    want = cr.encode_baseline(img, quality=90, subsampling=subsampling,
                              restart_interval=restart).jpeg_bytes
    assert corpus.encode(img, 90, subsampling, restart) == want


def _crops(blobs):
    """One crop answer per file, drawn as random-resized crops are."""
    rng = np.random.default_rng(3)
    for blob in blobs:
        fr = reference.parse(blob)
        h = int(rng.integers(fr.height // 3, fr.height + 1))
        w = int(rng.integers(fr.width // 3, fr.width + 1))
        top = int(rng.integers(0, fr.height - h + 1))
        left = int(rng.integers(0, fr.width - w + 1))
        yield blob, (top, left, h, w, 24, 20)


def _reference_crop_samples(blobs):
    out = []
    for blob, crop in _crops(blobs):
        fr, coeff = reference.coefficients(blob)
        rgb = reference.crop_resize(reference.pixels(fr, coeff), crop)
        out.append(Sample(blob, coeff, rgb, crop=crop))
    return out


def test_crop_answer_of_the_float64_reference_passes(blobs):
    samples = _reference_crop_samples(blobs)
    assert all(s.rgb.shape == (24, 20, 3) for s in samples)
    numbers = check.compare(samples, SerialPool(), resize=check.answers_spec(KINDS))
    assert numbers["coeff_wrong"] == 0 and numbers["rgb_off"] == 0
    ok, _ = check.verdict(numbers, {"missing": 0, "coeff_wrong": 0, "rgb_off": 1000})
    assert ok


@pytest.mark.parametrize("control", check.CONTROLS)
def test_each_control_fails_on_every_crop(blobs, control):
    """Every crop, but the bfloat16 control's on a grayscale file: its step
    below the stated precision is the colour conversion, which a
    one-component file has not, and its bfloat16 inverse DCT stays within 1
    (it reads 0 on the whole frames here); float8_idct fails those."""
    checked = 0
    for s in _reference_crop_samples(blobs):
        if control == "bfloat16" and len(reference.parse(s.blob).comps) == 1:
            continue
        numbers = check.compare([s], SerialPool(), control=control,
                                resize=reference.RESIZE)
        assert numbers["rgb_off"] > 1000, (control, s.crop, numbers)
        checked += 1
    assert checked >= 9


def test_crop_resize_is_bilinear_with_half_pixel_centres():
    img = np.array([[0, 100], [200, 40]], np.uint8)
    # 2x2 -> 4x4: samples at -0.25 (clamped to 0), 0.25, 0.75, 1.25 (to 1)
    out = reference.crop_resize(img, (0, 0, 2, 2, 4, 4))
    assert out.shape == (4, 4, 3) and (out[..., 0] == out[..., 2]).all()
    assert out[0, :, 0].tolist() == [0, 25, 75, 100]
    assert out[:, 0, 0].tolist() == [0, 50, 150, 200]
    assert out[1, 1, 0] == round(0.75 * 0.75 * 0 + 0.75 * 0.25 * 100
                                 + 0.25 * 0.75 * 200 + 0.25 * 0.25 * 40)
    with pytest.raises(ValueError, match="does not fit"):
        reference.crop_resize(img, (1, 0, 2, 2, 4, 4))


def test_crop_answers_without_a_stated_resize_are_refused(blobs):
    samples = _reference_crop_samples(blobs[:1])
    with pytest.raises(ValueError, match="answers.resize"):
        check.compare(samples, SerialPool())


def test_work_counts_a_crop_answer_by_its_output(blobs):
    blob, crop = next(_crops(blobs))
    whole, cut = work.image_work(blob), work.image_work(blob, crop)
    assert cut["out_bytes"] == 24 * 20 * 3
    assert whole["out_bytes"] == whole["pixels"] * whole["channels"]
    assert work.pixels_work([cut])["bytes"] == cut["blocks"] * 128 + 24 * 20 * 3


def _kinds(**change):
    cfg = json.loads(json.dumps(KINDS))
    cfg["corpus"]["kinds"][0].update(change)
    return cfg


REFUSED = {
    "tables": {"name": "t", "n_images": 1, "corpus": {
        "width": 16, "height": 16, "quality": 90, "subsampling": "4:2:0",
        "seed": 1, "tables": "optimized"}},
    "subsampling": _kinds(subsampling="4:1:1"),
    "key": {"name": "t", "n_images": 1, "corpus": {
        "width": 16, "height": 16, "quality": 90, "subsampling": "4:2:0",
        "seed": 1, "progressive": True}},
    "kind_key": _kinds(arithmetic=True),
    "shares": _kinds(share=0.6),
    "restart": _kinds(restart_interval=-1),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_corpus_the_generator_cannot_honour_is_refused(case):
    with pytest.raises(ValueError):
        corpus.start(REFUSED[case], RefusingPool())


def test_an_unknown_resize_is_refused():
    with pytest.raises(ValueError, match="answers.resize"):
        check.answers_spec(dict(KINDS, answers={"resize": "bicubic"}))
    with pytest.raises(ValueError, match="answers"):
        check.answers_spec(dict(KINDS, answers={"antialias": True}))
    assert check.answers_spec({"name": "no answers key"}) is None
