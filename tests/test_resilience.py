"""Resilient decode of damaged bitstreams (ISSUE 6).

The contract under test (docs/ROBUSTNESS.md):

* ``parse_jpeg`` failures are typed (`JpegFormatError` /
  `JpegTruncationError`) and carry byte offset + marker context;
* ``validate_blob``/``validate_batch`` NEVER raise — every blob is
  classified ok / recovered / rejected with diagnostics;
* a validated decode quarantines rejected images as inert lanes — the
  surviving images decode **bit-identically** to a clean batch, on every
  sync schedule and both backends;
* truncated-but-parseable scans recover their intact restart segments
  (``plan.seg_valid`` / ``plan.unit_valid`` masks);
* per-image status rides `DecodeOutput`/`JpegPipelineStats`/
  `decode_stats()`; quarantine adds no compiled-program cache entries;
* one corrupt feed must not take down a multi-host collective decode.

The corruption corpus (tests/_corrupt.py) is deterministic: CI fuzzes the
exact bytes a local run fuzzes.
"""
import numpy as np
import pytest

try:  # real hypothesis when installed; offline deterministic shim otherwise
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # pragma: no cover
    from _hypothesis_compat import given, settings
    from _hypothesis_compat import strategies as st

import _corrupt as cc
from _multiproc import run_hosts
from conftest import synth_image

from repro.core import (ParallelDecoder, STATUS_NAMES, STATUS_OK,
                        STATUS_RECOVERED, STATUS_REJECTED, build_batch_plan,
                        clear_decode_programs, decode_batch, decode_programs,
                        validate_batch, validate_blob)
from repro.jpeg import JpegFormatError, JpegTruncationError, parse_jpeg
from repro.jpeg import codec_ref as cr
from repro.jpeg.format import M_APP0, M_DHT, M_SOS


def _blob(seed=1, restart=0, quality=85, sub="4:4:4", size=(32, 32)):
    return cr.encode_baseline(synth_image(*size, seed=seed), quality=quality,
                              subsampling=sub,
                              restart_interval=restart).jpeg_bytes


def oracle(blob):
    p = cr.parse_jpeg(blob)
    return cr.undiff_dc(p, cr.decode_coefficients(p))


def _zero_app0_len(blob):
    """Unambiguously fatal header damage: APP0 length 0 (< the minimum 2)."""
    bad = bytearray(blob)
    off = dict(cc.marker_map(blob))[M_APP0]
    bad[off + 2: off + 4] = (0).to_bytes(2, "big")
    return bytes(bad)


def _cut_scan(blob, frac=3):
    """Truncate inside the entropy data (keeps all headers)."""
    start, end = cc.scan_span(blob)
    return blob[: start + (end - start) * (frac - 1) // frac]


# ---------------------------------------------------------------------------
# Satellite 1: typed, located parse errors
# ---------------------------------------------------------------------------

class TestTypedErrors:
    def test_header_error_carries_offset_and_marker(self):
        bad = _zero_app0_len(_blob())
        with pytest.raises(JpegFormatError) as ei:
            parse_jpeg(bad)
        assert ei.value.offset is not None
        assert ei.value.marker == M_APP0
        assert "0xFFE0" in str(ei.value) and "byte" in str(ei.value)

    def test_truncated_entropy_raises_typed_error(self):
        cut = _cut_scan(_blob(restart=2))
        with pytest.raises(JpegTruncationError):
            parse_jpeg(cut)
        # the truncation type is a JpegFormatError: existing handlers keep
        # working, new ones can special-case truncation
        assert issubclass(JpegTruncationError, JpegFormatError)

    def test_mid_segment_truncation_is_typed(self):
        blob = _blob()
        off = dict(cc.marker_map(blob))[M_DHT]
        with pytest.raises(JpegTruncationError) as ei:
            parse_jpeg(blob[: off + 6])  # cut inside the DHT payload
        assert ei.value.offset is not None

    def test_allow_truncated_parses_partial_scan(self):
        blob = _blob(restart=2)
        img = parse_jpeg(_cut_scan(blob), allow_truncated=True)
        assert img.truncated
        assert len(img.scan_data) > 0
        assert not parse_jpeg(blob, allow_truncated=True).truncated

    def test_not_a_jpeg_raises(self):
        for junk in (b"", b"\x00", b"not a jpeg at all", b"\xff\xd8"):
            with pytest.raises(JpegFormatError):
                parse_jpeg(junk)


# ---------------------------------------------------------------------------
# Tentpole: non-throwing classification
# ---------------------------------------------------------------------------

class TestValidateBlob:
    def test_clean_blob_is_ok(self):
        r = validate_blob(_blob(restart=2))
        assert r.status == STATUS_OK and r.error is None
        assert r.n_segments_actual == r.n_segments_expected > 1
        assert r.seg_valid.all() and r.clean is not None

    def test_header_damage_rejected_with_location(self):
        r = validate_blob(_zero_app0_len(_blob()))
        assert r.status == STATUS_REJECTED
        assert r.error_offset is not None and r.error_marker == M_APP0
        assert "length" in r.error

    def test_garbage_rejected_not_raised(self):
        for junk in (b"", b"\xff\xd8", b"x" * 100):
            assert validate_blob(junk).status == STATUS_REJECTED

    def test_truncated_scan_recovers_intact_segments(self):
        blob = _blob(restart=2)
        r = validate_blob(_cut_scan(blob))
        assert r.status == STATUS_RECOVERED
        assert "restart segments" in r.error
        assert 0 < r.n_segments_actual < r.n_segments_expected
        # intact prefix valid, the torn segment and the missing tail not
        n_valid = int(r.seg_valid.sum())
        assert 0 < n_valid < r.n_segments_expected
        assert r.seg_valid[:n_valid].all() and not r.seg_valid[n_valid:].any()

    def test_bad_huffman_table_rejected(self):
        # DHT counts mangled so declared values exceed the payload — the
        # silent crash surface build_decode_lut used to hit
        blob = _blob()
        off = dict(cc.marker_map(blob))[M_DHT]
        bad = bytearray(blob)
        bad[off + 5] = 0xFF
        r = validate_blob(bytes(bad))
        assert r.status == STATUS_REJECTED
        assert "DHT" in r.error or "huffman" in r.error.lower()

    def test_validate_batch_counts_and_errors(self):
        blobs = [_blob(seed=1, restart=2), _zero_app0_len(_blob(seed=2)),
                 _cut_scan(_blob(seed=3, restart=2)), _blob(seed=4, restart=2)]
        v = validate_batch(blobs)
        assert list(v.status) == [STATUS_OK, STATUS_REJECTED,
                                  STATUS_RECOVERED, STATUS_OK]
        assert (v.n_ok, v.n_recovered, v.n_rejected) == (2, 1, 1)
        assert not v.all_ok
        assert sorted(i for i, _ in v.errors()) == [1, 2]

    def test_validator_never_raises_on_corpus(self):
        """Every variant of the deterministic corruption corpus classifies
        without an exception."""
        for base_name, blob in cc.base_blobs(synth_image):
            for vname, bad in cc.corpus(blob, seed=0):
                r = validate_blob(bad)
                assert r.status in (STATUS_OK, STATUS_RECOVERED,
                                    STATUS_REJECTED), (base_name, vname)
                if r.status != STATUS_OK:
                    assert r.error, (base_name, vname)


# ---------------------------------------------------------------------------
# Tentpole: quarantine decode semantics
# ---------------------------------------------------------------------------

class TestQuarantineDecode:
    def test_clean_validated_plan_is_bit_identical_to_legacy(self):
        blobs = [_blob(seed=1, restart=2), _blob(seed=2, restart=2)]
        legacy = build_batch_plan(blobs, chunk_bits=256)
        val = build_batch_plan(blobs, chunk_bits=256,
                               validation=validate_batch(blobs))
        assert np.array_equal(legacy.words, val.words)
        assert np.array_equal(legacy.seg_nbits, val.seg_nbits)
        assert np.array_equal(legacy.unit_image, val.unit_image)
        assert val.seg_valid.all() and val.unit_valid.all()
        assert list(val.image_status) == [STATUS_OK, STATUS_OK]

    def test_mixed_batch_valid_images_bit_identical(self):
        clean = [_blob(seed=s, restart=2) for s in (1, 2, 3)]
        blobs = [clean[0], _zero_app0_len(clean[1]), clean[2]]
        out = decode_batch(blobs, chunk_bits=256, emit="rgb", validate=True)
        assert list(out.status) == [STATUS_OK, STATUS_REJECTED, STATUS_OK]
        assert out.converged
        coeffs = np.asarray(out.coeffs)
        n = cr.parse_jpeg(clean[0]).n_units  # uniform batch: equal footprints
        assert np.array_equal(coeffs[:n], oracle(clean[0]))
        assert np.array_equal(coeffs[2 * n:3 * n], oracle(clean[2]))
        # the quarantined lane is inert: all-zero coefficients, gray pixels
        assert not coeffs[n:2 * n].any()
        rgb = np.asarray(out.rgb)
        assert (rgb[1] == 128).all()
        assert rgb.shape[0] == 3

    def test_recovered_truncation_decodes_surviving_segments(self):
        blob = _blob(seed=5, restart=2)
        exp = oracle(blob)
        out = decode_batch([_cut_scan(blob)], chunk_bits=256, emit="coeffs",
                           validate=True)
        assert list(out.status) == [STATUS_RECOVERED]
        mask = out.plan.unit_valid
        assert 0 < mask.sum() < len(mask)
        got = np.asarray(out.coeffs)
        # every unit the validity mask claims decoded exactly as the
        # undamaged stream would have (restart-segment granularity, the
        # paper's intra-stream sync points)
        assert np.array_equal(got[mask], exp[mask])

    def test_all_rejected_batch_degrades_gracefully(self):
        blobs = [b"junk", _zero_app0_len(_blob())]
        out = decode_batch(blobs, chunk_bits=256, emit="rgb", validate=True)
        assert list(out.status) == [STATUS_REJECTED, STATUS_REJECTED]
        assert out.rgb is None  # no survivor to define the pixel layout

    def test_without_validate_corrupt_batch_raises(self):
        with pytest.raises(JpegFormatError):
            decode_batch([_zero_app0_len(_blob())], chunk_bits=256)

    def test_status_names_roundtrip(self):
        assert STATUS_NAMES[STATUS_OK] == "ok"
        assert STATUS_NAMES[STATUS_RECOVERED] == "recovered"
        assert STATUS_NAMES[STATUS_REJECTED] == "rejected"


# ---------------------------------------------------------------------------
# Satellite 3: property — mixed batches across schedules x backends
# ---------------------------------------------------------------------------

_CORRUPTIONS = ("flip", "trunc-scan", "trunc-header", "len", "rst", "junk")


def _corrupt_one(blob, kind, seed):
    rng = np.random.default_rng(seed)
    if kind == "flip":
        return cc.bit_flips(blob, seed=seed, n=1)[0][1]
    if kind == "trunc-scan":
        return _cut_scan(blob, frac=int(rng.integers(2, 6)))
    if kind == "trunc-header":
        variants = cc.truncations(blob)
        return variants[int(rng.integers(len(variants)))][1]
    if kind == "len":
        variants = cc.mangled_lengths(blob)
        return variants[int(rng.integers(len(variants)))][1]
    if kind == "rst":
        variants = cc.rst_mutations(blob)
        return variants[int(rng.integers(len(variants)))][1]
    return bytes(rng.integers(0, 256, size=64, dtype=np.uint8))


class TestPropertyMixedBatches:
    @settings(max_examples=12, deadline=None)
    @given(
        seed=st.integers(0, 10_000),
        kind=st.sampled_from(_CORRUPTIONS),
        sync=st.sampled_from(["jacobi", "faithful", "specmap", "sequential"]),
        backend=st.sampled_from(["jnp", "pallas"]),
    )
    def test_valid_images_unaffected_by_neighbors(self, seed, kind, sync,
                                                  backend):
        """A corrupt blob in the batch never crashes, never hangs, and
        never perturbs a single bit of the valid images' output — for any
        sync schedule and backend."""
        clean = _blob(seed=seed % 7, restart=2)
        bad = _corrupt_one(_blob(seed=seed % 7 + 50, restart=2), kind, seed)
        out = decode_batch([clean, bad], chunk_bits=256, seq_chunks=4,
                           emit="coeffs", sync=sync, backend=backend,
                           interpret=True, validate=True)
        assert out.status is not None and out.status[0] == STATUS_OK
        assert int(out.status[1]) in (STATUS_OK, STATUS_RECOVERED,
                                      STATUS_REJECTED)
        n = cr.parse_jpeg(clean).n_units
        assert np.array_equal(np.asarray(out.coeffs)[:n], oracle(clean))


# ---------------------------------------------------------------------------
# Satellite 4a: pipeline status plumbing
# ---------------------------------------------------------------------------

class TestPipelineResilience:
    def test_status_and_counters_through_pipeline(self):
        from repro.data.jpeg_pipeline import JpegVisionPipeline
        clean = [_blob(seed=s, restart=2) for s in (1, 2, 3)]
        pipe = JpegVisionPipeline(patch=16, embed_dim=32, chunk_bits=256,
                                  backend="jnp", validate=True)
        tokens, stats = pipe.patches_for(
            [clean[0], _zero_app0_len(clean[1]), clean[2]])
        assert tokens.shape[0] == 3
        assert list(stats.status) == [STATUS_OK, STATUS_REJECTED, STATUS_OK]
        assert (stats.images_recovered, stats.images_rejected) == (0, 1)
        pipe.patches_for([clean[0], _cut_scan(clean[1]), clean[2]])
        ds = pipe.decode_stats()
        assert ds["images_ok"] == 4
        assert ds["images_recovered"] == 1
        assert ds["images_rejected"] == 1

    def test_all_quarantined_batch_keeps_streaming(self):
        from repro.data.jpeg_pipeline import JpegVisionPipeline
        pipe = JpegVisionPipeline(patch=16, embed_dim=32, chunk_bits=256,
                                  backend="jnp", validate=True)
        tokens, stats = pipe.patches_for([b"junk", b"more junk"])
        # each quarantined image gives a zero crop, so zero tokens, and
        # the stream survives
        assert tokens.shape == (2, (224 // 16) ** 2, 32)
        assert not np.asarray(tokens, np.float32).any()
        assert list(stats.status) == [STATUS_REJECTED, STATUS_REJECTED]

    def test_unvalidated_pipeline_reports_no_status(self):
        from repro.data.jpeg_pipeline import JpegVisionPipeline
        pipe = JpegVisionPipeline(patch=16, embed_dim=32, chunk_bits=256,
                                  backend="jnp")
        _, stats = pipe.patches_for([_blob(seed=1)])
        assert stats.status is None
        assert pipe.decode_stats()["images_ok"] == 0

    def test_render_decode_stats_damage_columns(self):
        from repro.launch.report import render_decode_stats
        base = {"batches": 1, "compile_count": 1, "images_ok": 3}
        assert "rejected" not in render_decode_stats(base)
        txt = render_decode_stats(dict(base, images_rejected=2))
        assert "| ok | recovered | rejected |" in txt
        assert "| 3 | 0 | 2 |" in txt


# ---------------------------------------------------------------------------
# Satellite 4b: quarantine adds no compiled-program cache entries
# ---------------------------------------------------------------------------

class TestQuarantineCompileCache:
    def test_quarantined_batches_add_no_programs(self):
        """A damaged batch in a steady stream reuses an already-compiled
        covering shape — the bucket cache gains NO entry and NO retrace."""
        clear_decode_programs()
        kw = dict(chunk_bits=256, sync="jacobi", backend="jnp",
                  emit="coeffs", validate=True)
        for seeds in ((1, 2), (3, 4), (5, 6)):
            decode_batch([_blob(seed=s, restart=2) for s in seeds], **kw)
        progs = decode_programs()
        assert len(progs) == 1
        traces = sum(p.coeffs_traces for p in progs)
        clean = [_blob(seed=7, restart=2), _blob(seed=8, restart=2)]
        for damage in (_zero_app0_len, _cut_scan):
            out = decode_batch([clean[0], damage(clean[1])], **kw)
            assert int(out.status[1]) != STATUS_OK
        assert len(decode_programs()) == 1, \
            "quarantine must not mint new compile-cache entries"
        assert sum(p.coeffs_traces for p in decode_programs()) == traces


# ---------------------------------------------------------------------------
# Satellite 4c: multi-host — one corrupt feed must not strand the cluster
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestMultiHostResilience:
    def test_n2_one_host_fed_corrupt_blob(self):
        out = run_hosts("""
import numpy as np
from conftest import synth_image
from repro.jpeg import codec_ref as cr
from repro.launch.multihost import HostFeed, decode_multihost

corpus = [cr.encode_baseline(synth_image(32, 32, seed=s), quality=80,
                             restart_interval=2).jpeg_bytes
          for s in range(4)]
n_units = cr.parse_jpeg(corpus[0]).n_units
bad = bytearray(corpus[3])
bad[5] = 0x00  # APP0 length high byte -> fatal header damage on host 1
corpus[3] = bytes(bad)

feed = HostFeed.from_corpus(corpus, ctx)
out = decode_multihost(feed.local_blobs, ctx, chunk_bits=256, mesh="none",
                       assemble=False, validate=True)
coeffs = np.asarray(out.local.coeffs)
checks = []
for i, blob in enumerate(feed.local_blobs):
    block = coeffs[i * n_units:(i + 1) * n_units]
    if int(out.status[i]) == 0:
        p = cr.parse_jpeg(blob)
        exp = cr.undiff_dc(p, cr.decode_coefficients(p))
        checks.append(bool(np.array_equal(block, exp)))
    else:
        checks.append(bool(not block.any()))
emit({"pid": ctx.process_id, "statuses": [int(s) for s in out.status],
      "host_statuses": out.host_statuses, "checks": checks,
      "compiles": out.compiles, "converged": bool(out.local.converged)})
""", 2)
        assert out[0]["statuses"] == [0, 0]
        assert out[1]["statuses"] == [0, 2]
        for r in out:
            assert r["converged"]
            assert all(r["checks"]), f"host {r['pid']} decode mismatch"
            # statuses agreed cluster-wide over the coordination service
            assert r["host_statuses"] == [[0, 0], [0, 2]]
            # the damaged host still compiled exactly once (consensus shape)
            assert r["compiles"] == 1


# ---------------------------------------------------------------------------
# Satellite 6 backing: the fuzz smoke CI runs this module; make the decode
# fuzz itself deterministic and bounded
# ---------------------------------------------------------------------------

class TestFuzzDecodeSmoke:
    def test_corpus_decode_never_crashes(self):
        """Batches of corpus variants (each with one clean companion)
        decode without an exception; survivors converge. Bounded sample —
        the validator fuzz above covers the full corpus."""
        bases = cc.base_blobs(synth_image)
        for base_name, blob in bases[:2]:  # plain + rst2
            variants = cc.corpus(blob, seed=0)[::5]
            for i in range(0, len(variants), 4):
                group = [v for _, v in variants[i: i + 4]]
                out = decode_batch([blob] + group, chunk_bits=256,
                                   emit="coeffs", validate=True)
                assert out.status is not None
                assert int(out.status[0]) == STATUS_OK, base_name
                assert np.asarray(out.coeffs).shape[-1] == 64
