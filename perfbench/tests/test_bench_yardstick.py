"""The yardstick's parts on the CPU: the peak table, the work counts, the
corpus generator and reference decoder against the program's own, and the
trace reduction on a recorded trace."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import corpus, peaks, reference, trace, work  # noqa: E402


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_least_seconds_takes_the_larger_bound():
    t, bound = peaks.least_seconds(197e12, 819e9, "TPU v5 lite")
    assert bound in ("compute", "memory") and t == pytest.approx(1.0)
    t, bound = peaks.least_seconds(0.0, 819e9 * 2, "TPU v5 lite", chips=4)
    assert (t, bound) == (pytest.approx(0.5), "memory")


def test_work_counts_match_hand_arithmetic():
    frame = corpus.synth_frame(np.random.default_rng(0), 32, 16, 0.0)
    blob = corpus.encode(frame, 90, "4:2:0")
    w = work.image_work(blob)
    # 32x16 at 4:2:0: 2 MCUs of 16x16, each 4 luma + 2 chroma blocks
    assert w["blocks"] == 12 and w["pixels"] == 512 and w["channels"] == 3
    fr = reference.parse(blob)
    assert w["scan_bytes"] == len(fr.scan) > 0
    e = work.entropy_work([w, w])
    assert e == {"flops": 0.0, "bytes": 2.0 * (w["scan_bytes"] + 12 * 128)}
    p = work.pixels_work([w])
    assert p["flops"] == 12 * 2048
    assert p["bytes"] == 12 * 128 + 512 * 3


@pytest.mark.parametrize("size,quality,sampling", [
    ((64, 48), 95, "4:2:0"), ((72, 40), 50, "4:2:2"), ((40, 24), 80, "4:4:4")])
def test_corpus_copy_matches_the_programs_generator(size, quality, sampling):
    from repro.jpeg import codec_ref, encoder
    w, h = size
    mine = corpus.synth_frame(np.random.default_rng(3), w, h, 0.26)
    theirs = encoder.synth_frame(np.random.default_rng(3), w, h, 0.26)
    assert np.array_equal(mine, theirs)
    assert corpus.encode(mine, quality, sampling) == codec_ref.encode_baseline(
        theirs, quality=quality, subsampling=sampling).jpeg_bytes


@pytest.mark.parametrize("restart", [0, 3])
def test_reference_matches_the_programs_oracle(restart):
    from repro.jpeg import codec_ref as cr
    frame = corpus.synth_frame(np.random.default_rng(5), 80, 48, 0.5)
    blob = cr.encode_baseline(frame, quality=85, restart_interval=restart).jpeg_bytes
    coeff, rgb = reference.decode(blob)
    img = cr.parse_jpeg(blob)
    want = cr.undiff_dc(img, cr.decode_coefficients(img))
    assert np.array_equal(coeff, want)
    assert np.array_equal(rgb, cr.upsample_and_color(
        img, cr.coefficients_to_planes(img, want)))


# the largest difference each precision may make against float64 on one frame
MOVES = {"float64": 0, "bfloat16_idct": 2, "bfloat16": 4, "float8_idct": 8}


@pytest.mark.parametrize("precision", reference.PRECISIONS)
def test_each_precision_computes_the_same_pixels_roughly(precision):
    frame = corpus.synth_frame(np.random.default_rng(4), 64, 48, 0.3)
    fr, coeff = reference.coefficients(corpus.encode(frame, 95, "4:2:0"))
    exact = reference.pixels(fr, coeff).astype(int)
    rgb = reference.pixels(fr, coeff, precision)
    assert rgb.shape == exact.shape and rgb.dtype == np.uint8
    assert np.abs(rgb.astype(int) - exact).max() <= MOVES[precision]


def test_an_unknown_precision_is_refused():
    frame = corpus.synth_frame(np.random.default_rng(4), 16, 16, 0.0)
    fr, coeff = reference.coefficients(corpus.encode(frame, 95, "4:2:0"))
    with pytest.raises(ValueError, match="unknown precision"):
        reference.pixels(fr, coeff, "float16")


def test_corpus_is_one_fixed_set_cached_by_config(tmp_path, monkeypatch):
    class Pool:
        calls = 0

        def map_async(self, fn, tasks, chunksize=1):
            Pool.calls += 1

            class Result:
                def get(self):
                    return [fn(t) for t in tasks]
            return Result()

    monkeypatch.setattr(corpus, "CACHE_DIR", str(tmp_path))
    cfg = {"name": "tiny", "n_images": 3,
           "corpus": {"width": 32, "height": 16, "quality": 90,
                      "subsampling": "4:2:0", "seed": 9}}
    first = corpus.start(cfg, Pool()).get()
    again = corpus.start(cfg, Pool())
    assert again.get() == first and Pool.calls == 1
    assert "cache" in again.how
    assert len(first) == 3 and len(set(first)) == 3
    cfg["corpus"]["seed"] = 10
    assert corpus.start(cfg, Pool()).get() != first


def test_a_tagged_input_is_other_bytes_for_the_same_image():
    blob = corpus.encode(corpus.synth_frame(np.random.default_rng(2), 48, 32, 0.0),
                         90, "4:2:0")
    a, b = corpus.tagged(blob, "input 1"), corpus.tagged(blob, "input 2")
    assert len({blob, a, b}) == 3 and len(a) == len(b)
    assert reference.parse(a).scan == reference.parse(blob).scan
    for x, y in zip(reference.decode(a), reference.decode(blob)):
        assert np.array_equal(x, y)


def test_encoding_in_runs_of_units_gives_the_same_bytes(monkeypatch):
    frame = corpus.synth_frame(np.random.default_rng(4), 96, 64, 0.3)
    whole = corpus.encode(frame, 95, "4:2:0")
    monkeypatch.setattr(corpus, "UNITS_PER_RUN", 7)
    assert corpus.encode(frame, 95, "4:2:0") == whole


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace recorded on one TPU v5e: two batches of two 64x48 frames,
    each through ``from_bytes`` (``bench.plan``) and ``decode``
    (``bench.decode``), a 10 ms sleep between them, inside ``bench.window``."""
    import gzip
    import shutil
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(RECORDED_GZ, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce_xplane(str(path))


RECORDED_GZ = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "tiny.xplane.pb.gz")


def tiny_batch():
    return [corpus.encode(corpus.synth_frame(np.random.default_rng(i), 64, 48,
                                             0.1 * i), 95, "4:2:0")
            for i in range(2)]


def test_trace_reduces_to_known_numbers(recorded):
    assert recorded.window_s == pytest.approx(0.191070371, abs=1e-9)
    assert recorded.mean_busy_s() == pytest.approx(0.025595516, abs=1e-9)
    assert recorded.module_count(r"jit__coeffs\b") == 2
    assert recorded.module_count(r"jit__pixels\b") == 2
    assert recorded.module_s(r"jit__coeffs\b") == pytest.approx(0.025589652, abs=1e-9)
    gaps = recorded.idle_gaps(10 ** 6)
    assert sum(g[1] for g in gaps) + recorded.mean_busy_s() == pytest.approx(
        recorded.window_s, abs=1e-9)
    assert [g[0] for g in gaps[:3]] == ["bench.window", "bench.plan", "bench.decode"]
    assert gaps[0][1] == pytest.approx(0.143765869, abs=1e-9)
    assert recorded.top_ops(1) == [["%fusion.148 fusion", pytest.approx(0.003545668, abs=1e-9)]]


def test_metric_readers_on_the_recorded_trace(recorded):
    from harness import spec
    ctx = {"trace": recorded, "device_kind": "TPU v5 lite",
           "counters": {"batch_blobs": [tiny_batch(), tiny_batch()]}}
    assert spec.load_reader("idle_pct.ingest")(ctx) == pytest.approx(86.604142, abs=1e-5)
    # least time: 2 batches x (scan bytes + 2 x 72 blocks x 128 B) / 819 GB/s
    work_bytes = 2 * sum(w["scan_bytes"] + w["blocks"] * 128
                         for w in map(work.image_work, tiny_batch()))
    want = 100 * work_bytes / 819e9 / 0.025589652
    assert spec.load_reader("entropy_roofline")(ctx) == pytest.approx(want, rel=1e-6)
    assert spec.load_reader("pixels_roofline")(ctx) == pytest.approx(0.395544523, rel=1e-6)
    # a trace that does not hold one program run per batch reads nothing
    ctx["counters"]["batch_blobs"] = [tiny_batch()]
    assert spec.load_reader("entropy_roofline")(ctx) is None
