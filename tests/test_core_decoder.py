"""End-to-end tests for the parallel decoder (the paper's algorithm).

The central invariant: for every sync schedule, chunk size, subsampling
mode, and quality, the parallel decoder's coefficient output is *bit
identical* to the strict sequential oracle.
"""
import numpy as np
import pytest

try:  # real hypothesis when installed; offline deterministic shim otherwise
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from _hypothesis_compat import given, settings
    from _hypothesis_compat import strategies as st

from repro.core import (
    DecodeState,
    ParallelDecoder,
    build_batch_plan,
)
from repro.core import decode as D
from repro.core.sync import (
    chain_entries, faithful_sync, jacobi_sync, specmap_sync,
)
from repro.jpeg import codec_ref as cr

import jax
import jax.numpy as jnp

from conftest import synth_image


def oracle_coeffs(results):
    return np.concatenate(
        [cr.undiff_dc(r.image, cr.decode_coefficients(r.image)) for r in results]
    )


def encode_batch(n=3, h=48, w=64, quality=85, sub="4:2:0", **kw):
    imgs = [synth_image(h, w, seed=s) for s in range(n)]
    return [cr.encode_baseline(im, quality=quality, subsampling=sub, **kw) for im in imgs]


class TestParallelDecoder:
    @pytest.mark.parametrize("sync", ["sequential", "jacobi", "faithful"])
    @pytest.mark.parametrize("chunk_bits", [64, 128, 512])
    def test_exact_vs_oracle(self, sync, chunk_bits):
        results = encode_batch()
        dec = ParallelDecoder.from_bytes(
            [r.jpeg_bytes for r in results], chunk_bits=chunk_bits, sync=sync
        )
        out = dec.coefficients()
        assert out.converged
        assert np.array_equal(np.asarray(out.coeffs), oracle_coeffs(results))

    @pytest.mark.parametrize("sub", ["4:4:4", "4:2:2", "4:2:0"])
    def test_subsampling_modes(self, sub):
        results = encode_batch(sub=sub, n=2)
        dec = ParallelDecoder.from_bytes(
            [r.jpeg_bytes for r in results], chunk_bits=128
        )
        out = dec.coefficients()
        assert np.array_equal(np.asarray(out.coeffs), oracle_coeffs(results))

    @pytest.mark.parametrize("quality", [20, 55, 95])
    def test_quality_ladder(self, quality):
        results = encode_batch(quality=quality, n=2)
        dec = ParallelDecoder.from_bytes(
            [r.jpeg_bytes for r in results], chunk_bits=128
        )
        out = dec.coefficients()
        assert np.array_equal(np.asarray(out.coeffs), oracle_coeffs(results))

    def test_jacobi_equals_faithful_states(self):
        """Both schedules reach the same fixed point (sequential parse)."""
        results = encode_batch(n=2)
        blobs = [r.jpeg_bytes for r in results]
        plan = build_batch_plan(blobs, chunk_bits=128, seq_chunks=4)
        dev = {k: jnp.asarray(v) for k, v in plan.device_arrays().items()}
        ja = jacobi_sync(
            dev, s_max=plan.s_max, min_code_bits=plan.min_code_bits,
            max_rounds=plan.n_chunks + 2,
        )
        fa = faithful_sync(
            dev, s_max=plan.s_max, min_code_bits=plan.min_code_bits,
            seq_chunks=plan.seq_chunks, max_outer=plan.n_sequences + 2,
        )
        for a, b in zip(ja.exits, fa.exits):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_sequential_chunk_bits_sized_from_segments(self):
        """Regression: sequential mode sized its single chunk per segment
        from whole-*file* bytes, inflating s_max (the per-chunk decode loop
        bound) for every segment in the batch. It must be sized from the
        parsed scans' longest segment instead — and shrink accordingly."""
        results = encode_batch(n=3, restart_interval=2)
        blobs = [r.jpeg_bytes for r in results]
        dec = ParallelDecoder.from_bytes(blobs, sync="sequential")
        plan = dec.plan
        # still one chunk per segment (the sequential-baseline contract)
        assert plan.n_chunks == plan.n_segments
        assert plan.chunk_bits >= int(plan.seg_nbits.max())
        # the old file-sized bound, and the s_max it implied
        file_bits = -(-max(len(b) for b in blobs) * 8 // 32) * 32
        old_s_max = file_bits // plan.min_code_bits + 2
        assert plan.chunk_bits < file_bits
        assert plan.s_max < old_s_max
        out = dec.coefficients()
        assert out.converged
        assert np.array_equal(np.asarray(out.coeffs), oracle_coeffs(results))

    def test_restart_markers_as_segments(self):
        results = encode_batch(n=2, restart_interval=2)
        blobs = [r.jpeg_bytes for r in results]
        dec = ParallelDecoder.from_bytes(blobs, chunk_bits=96)
        out = dec.coefficients()
        assert np.array_equal(np.asarray(out.coeffs), oracle_coeffs(results))
        assert dec.plan.n_segments > 2  # restart split into multiple segments

    def test_rgb_matches_reference(self):
        results = encode_batch(n=2)
        dec = ParallelDecoder.from_bytes([r.jpeg_bytes for r in results],
                                         chunk_bits=128)
        out = dec.decode(emit="rgb")
        for i, r in enumerate(results):
            exp = cr.decode_baseline(r.jpeg_bytes)
            got = np.asarray(out.rgb[i])
            assert np.abs(got.astype(int) - exp.astype(int)).max() <= 1

    def test_optimized_huffman_tables(self):
        results = encode_batch(n=2, optimize_huffman=True)
        dec = ParallelDecoder.from_bytes([r.jpeg_bytes for r in results],
                                         chunk_bits=128)
        out = dec.coefficients()
        assert np.array_equal(np.asarray(out.coeffs), oracle_coeffs(results))

    def test_grayscale_batch(self):
        imgs = [synth_image(32, 32, seed=s)[..., 0] for s in range(2)]
        results = [cr.encode_baseline(im, quality=80) for im in imgs]
        dec = ParallelDecoder.from_bytes([r.jpeg_bytes for r in results],
                                         chunk_bits=96)
        out = dec.decode(emit="rgb")
        assert np.array_equal(np.asarray(out.coeffs), oracle_coeffs(results))
        assert out.rgb.shape == (2, 32, 32)

    def test_mixed_quality_batch(self):
        """Images with different tables in one batch (LUT dedup paths)."""
        blobs, results = [], []
        for q in (30, 60, 95):
            r = cr.encode_baseline(synth_image(48, 64, seed=q), quality=q)
            results.append(r)
            blobs.append(r.jpeg_bytes)
        dec = ParallelDecoder.from_bytes(blobs, chunk_bits=160)
        out = dec.coefficients()
        assert np.array_equal(np.asarray(out.coeffs), oracle_coeffs(results))

    @settings(max_examples=8, deadline=None)
    @given(
        seed=st.integers(0, 10_000),
        chunk_words=st.integers(2, 24),
        quality=st.sampled_from([25, 50, 75, 95]),
    )
    def test_property_any_chunking_is_exact(self, seed, chunk_words, quality):
        """Invariant: chunk framing never changes the decoded output."""
        img = synth_image(40, 40, seed=seed % 97, noise=25.0)
        r = cr.encode_baseline(img, quality=quality)
        dec = ParallelDecoder.from_bytes(
            [r.jpeg_bytes], chunk_bits=32 * chunk_words, sync="jacobi"
        )
        out = dec.coefficients()
        assert out.converged
        exp = cr.undiff_dc(r.image, cr.decode_coefficients(r.image))
        assert np.array_equal(np.asarray(out.coeffs), exp)


class TestSyncSchedulesAgree:
    """sync.py docstring claim: faithful and Jacobi schedules return
    bit-identical exit states — checked across random images and
    (chunk_bits, seq_chunks) framings."""

    @pytest.mark.parametrize("chunk_bits,seq_chunks", [(64, 2), (128, 4),
                                                       (256, 8)])
    def test_exit_states_bit_identical(self, chunk_bits, seq_chunks):
        imgs = [synth_image(40, 56, seed=10 + i, noise=18.0)
                for i in range(3)]
        blobs = [cr.encode_baseline(im, quality=q).jpeg_bytes
                 for im, q in zip(imgs, (35, 70, 92))]
        plan = build_batch_plan(blobs, chunk_bits=chunk_bits,
                                seq_chunks=seq_chunks)
        dev = {k: jnp.asarray(v) for k, v in plan.device_arrays().items()}
        ja = jacobi_sync(dev, s_max=plan.s_max,
                         min_code_bits=plan.min_code_bits,
                         max_rounds=plan.n_chunks + 2)
        fa = faithful_sync(dev, s_max=plan.s_max,
                           min_code_bits=plan.min_code_bits,
                           seq_chunks=plan.seq_chunks,
                           max_outer=plan.n_sequences + 2)
        assert bool(ja.converged) and bool(fa.converged)
        for a, b in zip(ja.exits, fa.exits):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def _stage(dev, chunk_bits):
    lane_words, lane_rows = D.stage_lanes(dev, D.stage_words(chunk_bits))
    return dict(dev, lane_words=lane_words, lane_rows=lane_rows)


def _schedule(sync, dev, sh):
    """The entropy program's call of each schedule, as core/api builds it."""
    from repro.core.bitstream import MAX_UPM
    kw = dict(s_max=sh.s_max, min_code_bits=sh.min_code_bits,
              permuted=sh.permuted)
    if sync == "jacobi":
        return jacobi_sync(dev, max_rounds=sh.n_chunks + 2, **kw)
    if sync == "faithful":
        return faithful_sync(dev, seq_chunks=sh.seq_chunks,
                             max_outer=sh.n_sequences + 2, **kw)
    return specmap_sync(dev, max_upm=MAX_UPM,
                        max_verify=sh.n_chunks + MAX_UPM + 2, **kw)


class TestStagedStep:
    """The symbol step that reads staged lane operands by a one-hot select
    gives what the gather form gives, bit for bit, and the form follows the
    chunk's width W alone."""

    @staticmethod
    def _decoder(chunk_bits, balance="none", sync="jacobi"):
        imgs = [synth_image(40, 56, seed=10 + i, noise=18.0)
                for i in range(3)]
        blobs = [cr.encode_baseline(im, quality=q).jpeg_bytes
                 for im, q in zip(imgs, (35, 70, 92))]
        return ParallelDecoder.from_bytes(
            blobs, chunk_bits=chunk_bits, seq_chunks=2, sync=sync,
            balance=balance, lanes=2)

    @pytest.mark.parametrize("chunk_bits", [128, 1024])
    @pytest.mark.parametrize("balance", ["none", "lpt"])
    @pytest.mark.parametrize("sync", ["jacobi", "faithful", "specmap"])
    def test_staged_step_matches_gather_form(self, sync, balance, chunk_bits,
                                             monkeypatch):
        from repro.core import api
        dec = self._decoder(chunk_bits, balance, sync)
        sh = dec.shape
        assert sh.n_units > dec.plan.total_units     # capacity-padded
        assert sh.permuted == (balance != "none")
        dev = dec.dev

        run = jax.jit(lambda d: _schedule(sync, d, sh))
        gather, staged = run(dev), run(_stage(dev, sh.chunk_bits))
        assert bool(gather.converged) and bool(staged.converged)
        assert int(gather.rounds) == int(staged.rounds)
        for a, b in zip(gather.exits, staged.exits):
            assert np.array_equal(np.asarray(a), np.asarray(b))

        def coeffs(prog):
            return prog.call_coeffs(dec.data.words, dec._dev_rest, None)

        staged_prog = api._build_program(sh, sync, "jnp", None, None)
        monkeypatch.setattr(D, "STAGE_MAX_WORDS", 0)
        gather_prog = api._build_program(sh, sync, "jnp", None, None)
        assert staged_prog.step_staged and not gather_prog.step_staged
        (c_g, r_g, ok_g), (c_s, r_s, ok_s) = (coeffs(gather_prog),
                                              coeffs(staged_prog))
        assert bool(ok_g) and bool(ok_s)
        assert int(r_g) == int(r_s) == int(staged.rounds)
        assert np.array_equal(np.asarray(c_g), np.asarray(c_s))

    @pytest.mark.parametrize("quality,step", [(90, "gather"), (35, "staged")])
    def test_sequential_form_follows_its_segment_width(self, quality, step):
        """Sequential chunks are whole segments: past STAGE_MAX_WORDS words
        they keep the gather form; a batch of tiny segments stages."""
        r = cr.encode_baseline(synth_image(48, 64, seed=0), quality=quality)
        dec = ParallelDecoder.from_bytes([r.jpeg_bytes], sync="sequential")
        assert dec.program.step == step
        assert (D.stage_words(dec.shape.chunk_bits) is None) == (
            step == "gather")
        out = dec.coefficients()
        assert np.array_equal(np.asarray(out.coeffs), oracle_coeffs([r]))

    @pytest.mark.parametrize("chunk_bits", [128, 1024])
    @pytest.mark.parametrize("entries", ["garbage", "converged"])
    def test_active_lanes_select_inside_their_words(self, entries,
                                                    chunk_bits):
        """Every active lane's window lies at staged words k and k + 1 with
        0 <= k <= W - 2, at every step, and both forms step alike: from the
        chained exits of the cold pass (round 2's entries, whose
        predecessors ran past their limits) and from converged exits."""
        dec = self._decoder(chunk_bits)
        sh, dev = dec.shape, dec.dev
        w = D.stage_words(sh.chunk_bits)
        sdev = _stage(dev, sh.chunk_bits)
        m, ms = D.chunk_meta(dev), D.chunk_meta(sdev)
        exits_fn = D.make_decode_exits(s_max=sh.s_max,
                                       min_code_bits=sh.min_code_bits)
        if entries == "garbage":
            exits = exits_fn(dev, DecodeState.cold(dev["chunk_start"]))
        else:
            exits = _schedule("jacobi", dev, sh).exits
        entry = chain_entries(dev, exits)
        past = np.asarray(entry.p) > np.asarray(dev["chunk_start"])
        assert past.any()

        def body(carry, _):
            sg, ss = carry
            og = D.decode_symbol(dev, sg, m["word_base"], m["limit"], m["ts"],
                                 m["upm"], sh.min_code_bits)
            os_ = D.decode_symbol(sdev, ss, m["word_base"], m["limit"],
                                  m["ts"], m["upm"], sh.min_code_bits,
                                  ms["stage"])
            k = (ss.p >> 5) - ms["stage"].word0
            act = ss.p < m["limit"]
            same = (jnp.all(og.active == os_.active)
                    & jnp.all(og.state.puz_equal(os_.state))
                    & jnp.all(og.state.n == os_.state.n)
                    & jnp.all(jnp.where(og.active, og.coef == os_.coef, True)))
            return (og.state, os_.state), (
                jnp.min(jnp.where(act, k, w)), jnp.max(jnp.where(act, k, -1)),
                jnp.sum(act), same)

        st0 = DecodeState(entry.p, entry.u, entry.z, jnp.zeros_like(entry.p))
        _, (k_lo, k_hi, n_act, same) = jax.jit(lambda s: jax.lax.scan(
            body, (s, s), None, length=sh.s_max))(st0)
        assert int(n_act[0]) > 0 and int(n_act[-1]) == 0
        # the window's two words: the chunk's own and the one it straddles
        # into, inside the W staged
        assert int(k_lo.min()) >= 0
        assert int(k_hi.max()) <= sh.chunk_bits // 32 - 1 <= w - 2
        assert bool(np.all(same))


class TestDecodeEdgePaths:
    def _mixed_geometry(self):
        """Two images whose scan geometry differs -> non-uniform plan."""
        results = [
            cr.encode_baseline(synth_image(48, 64, seed=0), quality=80),
            cr.encode_baseline(synth_image(32, 32, seed=1), quality=80),
        ]
        dec = ParallelDecoder.from_bytes(
            [r.jpeg_bytes for r in results], chunk_bits=128)
        assert not dec.plan.uniform
        return results, dec

    def test_coeffs_on_mixed_geometry_batch(self):
        results, dec = self._mixed_geometry()
        out = dec.decode(emit="coeffs")
        assert out.planes is None and out.rgb is None
        assert np.array_equal(np.asarray(out.coeffs), oracle_coeffs(results))

    def test_pixel_stage_on_mixed_geometry_raises(self):
        _, dec = self._mixed_geometry()
        with pytest.raises(NotImplementedError,
                           match="geometry-uniform batch"):
            dec.decode(emit="rgb")


class TestCoeffCapacityGuard:
    """device_arrays ships seg_coeff_base as int32; a batch with >= 2**25
    data units would silently wrap the write offsets. build_batch_plan must
    refuse loudly instead (synthetic sizes — a real batch that big would
    need gigapixels of JPEG)."""

    def test_guard_boundary(self):
        from repro.core.bitstream import check_coeff_capacity

        check_coeff_capacity(2 ** 25 - 1)  # last addressable size: fine
        with pytest.raises(ValueError, match="int32"):
            check_coeff_capacity(2 ** 25)
        with pytest.raises(ValueError, match="overflows"):
            check_coeff_capacity(2 ** 30)

    def test_build_batch_plan_calls_guard(self, monkeypatch):
        import repro.core.bitstream as B

        seen = {}

        def spy(total_units, s_max=0):
            seen["units"] = total_units
            seen["s_max"] = s_max
            return None

        monkeypatch.setattr(B, "check_coeff_capacity", spy)
        results = encode_batch(n=2)
        plan = B.build_batch_plan([r.jpeg_bytes for r in results],
                                  chunk_bits=128)
        assert seen["units"] == plan.total_units
        # the guard sees the worst-case single-chunk overshoot too
        assert seen["s_max"] == plan.s_max > 0

    def test_small_batches_unaffected(self):
        results = encode_batch(n=1, h=16, w=16)
        plan = build_batch_plan([r.jpeg_bytes for r in results],
                                chunk_bits=128)
        assert plan.total_units * 64 < 2 ** 31


class TestDecodeInternals:
    def test_fetch_window32(self):
        words = jnp.asarray(
            np.array([0xDEADBEEF, 0x12345678, 0], dtype=np.uint32)
        )
        base = jnp.zeros(3, jnp.int32)
        p = jnp.asarray([0, 4, 32], jnp.int32)
        got = D.fetch_window32(words, base, p)
        assert int(got[0]) == 0xDEADBEEF
        assert int(got[1]) == 0xEADBEEF1
        assert int(got[2]) == 0x12345678

    def test_segmented_cumsum_resets(self):
        vals = jnp.asarray([1, 2, 3, 4, 5], jnp.int32)
        first = jnp.asarray([True, False, False, True, False])
        out = D.segmented_exclusive_cumsum(vals, first)
        assert out.tolist() == [0, 1, 3, 0, 4]

    def test_cold_state(self):
        st_ = DecodeState.cold(jnp.asarray([0, 128], jnp.int32))
        assert st_.p.tolist() == [0, 128]
        assert st_.u.tolist() == [0, 0]
        assert st_.z.tolist() == [0, 0]
