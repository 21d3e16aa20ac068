"""Integration: the paper's decoder as the VLM input pipeline."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.jpeg_pipeline import JpegVisionPipeline
from repro.jpeg.encoder import DatasetSpec, build_dataset


def test_pipeline_patches_shape_and_stats():
    ds = build_dataset(DatasetSpec("t", n_images=4, width=64, height=48,
                                   quality=80))
    pipe = JpegVisionPipeline(patch=8, embed_dim=64, chunk_bits=256)
    patches, stats = pipe.patches_for(ds.jpeg_bytes)
    assert patches.shape == (4, (48 // 8) * (64 // 8), 64)
    assert patches.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(patches, np.float32)).all()
    assert stats.n_images == 4
    assert stats.transfer_saving > 1.0  # decoded >> compressed
    assert stats.compressed_mb > 0


def test_decoder_cache_keys_on_content_not_shape():
    """Regression: the compiled-decoder cache used to key on
    (len(blobs), total_bytes), so two different batches of equal count and
    total size silently reused the first batch's device words and decoded
    the wrong images. Reversing a 2-image batch keeps (count, total_bytes)
    identical while changing every output pixel."""
    ds = build_dataset(DatasetSpec("t3", n_images=2, width=64, height=48,
                                   quality=80))
    a, b = ds.jpeg_bytes
    pipe = JpegVisionPipeline(patch=8, embed_dim=32, chunk_bits=256)
    tok_ab, _ = pipe.patches_for([a, b])
    tok_ba, _ = pipe.patches_for([b, a])  # same (count, total_bytes)!
    assert len(pipe._decoders) == 2  # distinct compiled decoders
    # each batch decodes its own images, in its own order
    fresh = JpegVisionPipeline(patch=8, embed_dim=32, chunk_bits=256)
    exp_ba, _ = fresh.patches_for([b, a])
    np.testing.assert_array_equal(
        np.asarray(tok_ba, np.float32), np.asarray(exp_ba, np.float32))
    np.testing.assert_array_equal(
        np.asarray(tok_ab[0], np.float32), np.asarray(tok_ba[1], np.float32))
    assert not np.array_equal(np.asarray(tok_ab, np.float32),
                              np.asarray(tok_ba, np.float32))


def test_decoder_cache_is_bounded_lru():
    """Content-keyed caching must not retain a decoder (and its on-device
    batch words) for every distinct batch ever seen."""
    ds = build_dataset(DatasetSpec("t5", n_images=4, width=32, height=32,
                                   quality=70))
    blobs = ds.jpeg_bytes
    pipe = JpegVisionPipeline(patch=8, embed_dim=32, chunk_bits=128,
                              decoder_cache_size=2)
    batches = [[blobs[i]] for i in range(3)]
    for b in batches:
        pipe.patches_for(b)
    assert len(pipe._decoders) == 2
    # oldest entry evicted; most recent two retained
    assert pipe._batch_key(batches[0]) not in pipe._decoders
    assert pipe._batch_key(batches[2]) in pipe._decoders
    # a hit refreshes recency: touch batch 1, insert batch 0, batch 2 evicts
    pipe.patches_for(batches[1])
    pipe.patches_for(batches[0])
    assert pipe._batch_key(batches[1]) in pipe._decoders
    assert pipe._batch_key(batches[2]) not in pipe._decoders
    # size 0 = cache bypass, not a crash
    nocache = JpegVisionPipeline(patch=8, embed_dim=32, chunk_bits=128,
                                 decoder_cache_size=0)
    nocache.patches_for(batches[0])
    assert len(nocache._decoders) == 0


def test_cache_size_zero_streams_through_shared_programs():
    """Regression (bucket-cache eviction semantics): decoder_cache_size=0
    must return a fresh, fully usable decoder every call, pin nothing in
    the pipeline afterwards, and still reuse the *shared* per-bucket
    compiled program — eviction drops a batch's device arrays, never a
    compilation."""
    from repro.core import clear_decode_programs, decode_programs
    clear_decode_programs()
    ds = build_dataset(DatasetSpec("t7", n_images=2, width=32, height=32,
                                   quality=70))
    pipe = JpegVisionPipeline(patch=8, embed_dim=32, chunk_bits=128,
                              decoder_cache_size=0)
    tok1, st1 = pipe.patches_for(ds.jpeg_bytes)
    assert len(pipe._decoders) == 0 and st1.compiled
    # the decoder handle built mid-call was usable and is now unreferenced;
    # decoding the SAME batch again rebuilds a handle but must not retrace
    tok2, st2 = pipe.patches_for(ds.jpeg_bytes)
    assert len(pipe._decoders) == 0 and not st2.compiled
    np.testing.assert_array_equal(np.asarray(tok1, np.float32),
                                  np.asarray(tok2, np.float32))
    assert all(p.coeffs_traces == 1 and p.pixels_traces == 1
               for p in decode_programs())
    # _decoder itself still hands back a working decoder at size 0
    dec = pipe._decoder(ds.jpeg_bytes)
    assert dec.decode(emit="coeffs").converged
    assert len(pipe._decoders) == 0


def test_pipeline_backend_knob():
    """backend="pallas" threads through to the decoder and yields the same
    tokens as the jnp reference."""
    ds = build_dataset(DatasetSpec("t4", n_images=2, width=32, height=32,
                                   quality=75))
    ref = JpegVisionPipeline(patch=8, embed_dim=32, chunk_bits=128)
    pal = JpegVisionPipeline(patch=8, embed_dim=32, chunk_bits=128,
                             backend="pallas")
    tok_ref, _ = ref.patches_for(ds.jpeg_bytes)
    tok_pal, _ = pal.patches_for(ds.jpeg_bytes)
    assert next(iter(pal._decoders.values())).backend == "pallas"
    np.testing.assert_array_equal(
        np.asarray(tok_ref, np.float32), np.asarray(tok_pal, np.float32))


def test_pipeline_batches_iterator():
    ds = build_dataset(DatasetSpec("t2", n_images=6, width=32, height=32,
                                   quality=70))
    pipe = JpegVisionPipeline(patch=8, embed_dim=32, chunk_bits=128)
    batches = list(pipe.batches(ds, batch_size=3))
    assert len(batches) == 2
    for patches, stats in batches:
        assert patches.shape[0] == 3


def test_pipeline_batches_yields_tail():
    """Regression: batches() silently dropped the last
    len(blobs) % batch_size images. The tail must come back as a short
    final batch unless drop_remainder=True is asked for."""
    ds = build_dataset(DatasetSpec("t6", n_images=7, width=32, height=32,
                                   quality=70))
    pipe = JpegVisionPipeline(patch=8, embed_dim=32, chunk_bits=128)
    batches = list(pipe.batches(ds, batch_size=3))
    assert [p.shape[0] for p, _ in batches] == [3, 3, 1]
    assert sum(s.n_images for _, s in batches) == 7
    # fixed-shape training streams can opt back into dropping
    dropped = list(pipe.batches(ds, batch_size=3, drop_remainder=True))
    assert [p.shape[0] for p, _ in dropped] == [3, 3]
    # a batch size larger than the dataset still yields everything
    assert [p.shape[0] for p, _ in pipe.batches(ds, batch_size=10)] == [7]
    assert list(pipe.batches(ds, batch_size=10, drop_remainder=True)) == []


def test_paper_datasets_registry():
    from repro.jpeg.encoder import PAPER_DATASETS, scaled_spec
    assert set(PAPER_DATASETS) == {
        "newyork", "stata", "tos_1440p", "tos_4k", "tos_8", "tos_14", "tos_20"}
    s = scaled_spec(PAPER_DATASETS["newyork"], 0.01)
    assert s.n_images >= 2 and s.width % 16 == 0
    # quality ladder ordering preserved
    assert (PAPER_DATASETS["tos_8"].quality > PAPER_DATASETS["tos_14"].quality
            > PAPER_DATASETS["tos_20"].quality)


@pytest.mark.parametrize("given", [False, True], ids=["centre", "given"])
def test_mixed_batch_gives_tokens_of_its_crops(given):
    """A mixed batch is decoded to 224x224 crops: by default the centre
    square of each frame's shorter side, or the boxes given; its tokens are
    those of the crop batch, 196 per image at patch 16."""
    from repro.core import decode_batch
    from repro.jpeg import codec_ref as cr
    imgs = [(48, 64, "4:2:0"), (40, 24, "4:4:4"), (33, 45, "gray")]
    rng = np.random.default_rng(4)
    blobs = []
    for h, w, s in imgs:
        img = rng.integers(0, 256, (h, w, 3)).astype(np.uint8)
        blobs.append(cr.encode_baseline(img[..., 0] if s == "gray" else img,
                                        quality=85, subsampling=s).jpeg_bytes)
    boxes = (np.array([[4, 2, 30, 40], [0, 0, 40, 24], [1, 3, 20, 20]])
             if given else
             np.array([[0, 8, 48, 48], [8, 0, 24, 24], [0, 6, 33, 33]]))
    pipe = JpegVisionPipeline(patch=16, embed_dim=32, chunk_bits=256)
    tokens, stats = pipe.patches_for(blobs, crops=boxes if given else None)
    assert tokens.shape == (3, 196, 32)
    crop = decode_batch(blobs, chunk_bits=256, emit="crop", crops=boxes).crop
    x = (np.asarray(crop, np.float32) / 255.0).reshape(3, 14, 16, 14, 16, 3)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(3, 196, 768)
    exp = jnp.asarray(x, jnp.bfloat16) @ pipe.w_embed
    np.testing.assert_array_equal(np.asarray(tokens, np.float32),
                                  np.asarray(exp, np.float32))
    assert stats.decoded_mb == 3 * 224 * 224 * 3 / 1e6
