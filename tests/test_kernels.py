"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles.

Sweeps shapes/dtypes per the harness contract; the huffman kernel is
additionally validated against the sequential-oracle-exact core decoder on
real bitstreams, and the backend knob (schedule × backend parity matrix)
against the sequential oracle end-to-end.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import build_batch_plan, DecodeState, ParallelDecoder
from repro.core import decode as D
from repro.core.bitstream import folded_idct_matrix
from repro.jpeg import codec_ref as cr
from repro.jpeg import tables as T
from repro.kernels import backend as KB
from repro.kernels.idct.ops import idct_units
from repro.kernels.idct.ref import fused_idct_ref
from repro.kernels.huffman.ops import decode_coeffs, decode_exits
from repro.kernels.huffman.ref import decode_exits_ref
from repro.kernels.color.color import upsample_color
from repro.kernels.color.ref import upsample_color_ref

from conftest import synth_image


class TestIdctKernel:
    @pytest.mark.parametrize("n_units", [1, 7, 512, 1000])
    @pytest.mark.parametrize("nq", [1, 2, 3])
    def test_matches_ref(self, n_units, nq, rng):
        coeffs = rng.integers(-512, 512, (n_units, 64)).astype(np.int32)
        mats = np.stack(
            [folded_idct_matrix(T.quality_scaled_quant(T.STD_LUMA_QUANT, q))
             for q in (40, 75, 95)[:nq]]
        )
        rows = rng.integers(0, nq, n_units).astype(np.int32)
        got = idct_units(jnp.asarray(coeffs), jnp.asarray(mats), jnp.asarray(rows))
        exp = fused_idct_ref(jnp.asarray(coeffs), jnp.asarray(mats), jnp.asarray(rows))
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=1e-3)

    def test_matches_scalar_idct_pipeline(self, rng):
        """Folded matmul == dezigzag -> dequant -> classic separable IDCT."""
        q = T.quality_scaled_quant(T.STD_LUMA_QUANT, 80)
        coeffs = rng.integers(-64, 64, (32, 64)).astype(np.int32)
        mats = folded_idct_matrix(q)[None]
        got = idct_units(jnp.asarray(coeffs), jnp.asarray(mats),
                         jnp.zeros(32, jnp.int32))
        nat = np.zeros_like(coeffs)
        nat[:, T.ZIGZAG] = coeffs
        deq = (nat * q[None]).reshape(-1, 8, 8).astype(np.float64)
        exp = np.clip(np.round(cr.idct_units(deq).reshape(-1, 64) + 128), 0, 255)
        np.testing.assert_allclose(np.asarray(got), exp, atol=1e-3)

    @pytest.mark.parametrize("dtype", [np.int32, np.float32])
    def test_dtype_sweep(self, dtype, rng):
        coeffs = rng.integers(-100, 100, (64, 64)).astype(dtype)
        mats = folded_idct_matrix(T.STD_LUMA_QUANT)[None]
        rows = np.zeros(64, np.int32)
        got = idct_units(jnp.asarray(coeffs), jnp.asarray(mats), jnp.asarray(rows))
        exp = fused_idct_ref(jnp.asarray(coeffs), jnp.asarray(mats), jnp.asarray(rows))
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=1e-3)


class TestHuffmanKernel:
    def _plan_dev(self, n=2, chunk_bits=128, quality=85, sub="4:2:0"):
        imgs = [synth_image(48, 64, seed=s) for s in range(n)]
        blobs = [cr.encode_baseline(im, quality=quality, subsampling=sub).jpeg_bytes
                 for im in imgs]
        plan = build_batch_plan(blobs, chunk_bits=chunk_bits)
        dev = {k: jnp.asarray(v) for k, v in plan.device_arrays().items()}
        return plan, dev

    @pytest.mark.parametrize("chunk_bits", [64, 128, 1024])
    @pytest.mark.parametrize("sub", ["4:4:4", "4:2:0"])
    def test_cold_exits_match_ref(self, chunk_bits, sub):
        plan, dev = self._plan_dev(chunk_bits=chunk_bits, sub=sub)
        entry = DecodeState.cold(dev["chunk_start"])
        meta = D.chunk_meta(dev)
        exp = decode_exits_ref(dev, entry, meta["word_base"], meta["limit"],
                               meta["ts"], meta["upm"], s_max=plan.s_max,
                               min_code_bits=plan.min_code_bits)
        got = decode_exits(dev, entry, s_max=plan.s_max,
                           min_code_bits=plan.min_code_bits,
                           chunk_bits=plan.chunk_bits)
        for a, b in zip(got, exp):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_overflow_entries_match_ref(self):
        """Entry states mid-chunk (the overflow pattern) decode identically."""
        from repro.core.sync import chain_entries, jacobi_sync

        plan, dev = self._plan_dev(chunk_bits=128)
        res = jacobi_sync(dev, s_max=plan.s_max,
                          min_code_bits=plan.min_code_bits,
                          max_rounds=plan.n_chunks + 2)
        entry = chain_entries(dev, res.exits)
        meta = D.chunk_meta(dev)
        exp = decode_exits_ref(dev, entry, meta["word_base"], meta["limit"],
                               meta["ts"], meta["upm"], s_max=plan.s_max,
                               min_code_bits=plan.min_code_bits)
        got = decode_exits(dev, entry, s_max=plan.s_max,
                           min_code_bits=plan.min_code_bits,
                           chunk_bits=plan.chunk_bits)
        for a, b in zip(got, exp):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_chunk_subset_gather_matches_ref(self):
        """decode_exits(idx=...) — the faithful_sync decode_at path — must
        equal the jnp reference decoded at the same chunk subset."""
        from repro.core.sync import chain_entries, jacobi_sync

        plan, dev = self._plan_dev(chunk_bits=128)
        res = jacobi_sync(dev, s_max=plan.s_max,
                          min_code_bits=plan.min_code_bits,
                          max_rounds=plan.n_chunks + 2)
        entries = chain_entries(dev, res.exits)
        idx = jnp.asarray(
            np.random.default_rng(3).permutation(plan.n_chunks)[: max(
                2, plan.n_chunks // 2)].astype(np.int32))
        entry = DecodeState(entries.p[idx], entries.u[idx], entries.z[idx],
                            entries.n[idx])
        meta = D.chunk_meta(dev, idx)
        exp = decode_exits_ref(dev, entry, meta["word_base"], meta["limit"],
                               meta["ts"], meta["upm"], s_max=plan.s_max,
                               min_code_bits=plan.min_code_bits)
        got = decode_exits(dev, entry, idx, s_max=plan.s_max,
                           min_code_bits=plan.min_code_bits,
                           chunk_bits=plan.chunk_bits)
        for a, b in zip(got, exp):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_write_pass_matches_jnp_scatter(self):
        """The Pallas write pass (Alg. 1 lines 9-15) reproduces the jnp
        per-symbol scatter bit-for-bit from converged entries."""
        from repro.core.sync import chain_entries, jacobi_sync

        plan, dev = self._plan_dev(chunk_bits=128)
        res = jacobi_sync(dev, s_max=plan.s_max,
                          min_code_bits=plan.min_code_bits,
                          max_rounds=plan.n_chunks + 2)
        entries = chain_entries(dev, res.exits)
        bases = D.chunk_write_bases(dev, res.exits.n)
        seg_end = jnp.concatenate([
            dev["seg_coeff_base"][1:],
            jnp.asarray([plan.total_units * 64], dtype=jnp.int32),
        ])
        write_max = seg_end[dev["chunk_seg"]] - 1
        meta = D.chunk_meta(dev)
        out0 = jnp.zeros((plan.total_units * 64,), jnp.int32)
        _, exp = D.decode_span(
            dev, entries, meta["word_base"], meta["limit"], meta["ts"],
            meta["upm"], s_max=plan.s_max, min_code_bits=plan.min_code_bits,
            write=True, out=out0, write_base=bases, write_max=write_max,
        )
        exits, got = decode_coeffs(
            dev, entries, out=out0, write_base=bases, write_max=write_max,
            s_max=plan.s_max, min_code_bits=plan.min_code_bits,
            chunk_bits=plan.chunk_bits,
        )
        assert np.array_equal(np.asarray(got), np.asarray(exp))
        for a, b in zip(exits, res.exits):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def _mixed_quality_batch():
    blobs, results = [], []
    for q in (30, 60, 95):
        r = cr.encode_baseline(synth_image(48, 64, seed=q), quality=q,
                               subsampling="4:2:0")
        results.append(r)
        blobs.append(r.jpeg_bytes)
    exp = np.concatenate(
        [cr.undiff_dc(r.image, cr.decode_coefficients(r.image))
         for r in results]
    )
    return blobs, exp


class TestBackendParityMatrix:
    """Acceptance: decode_batch(..., backend="pallas") is bit-identical to
    backend="jnp" and the sequential oracle for every sync schedule on a
    mixed-quality batch (the 8-device mesh variant lives in
    tests/test_distribution.py)."""

    @pytest.mark.parametrize(
        "sync", ["jacobi", "faithful", "specmap", "sequential"])
    def test_coeffs_bit_identical_across_backends(self, sync):
        blobs, exp = _mixed_quality_batch()
        outs = {}
        for backend in ("jnp", "pallas"):
            dec = ParallelDecoder.from_bytes(
                blobs, chunk_bits=160, sync=sync, backend=backend,
                interpret=True)
            out = dec.coefficients()
            assert out.converged
            outs[backend] = np.asarray(out.coeffs)
        assert np.array_equal(outs["jnp"], exp)
        assert np.array_equal(outs["pallas"], exp)

    @pytest.mark.parametrize("sync", ["jacobi", "faithful", "specmap"])
    def test_exit_states_bit_identical_across_backends(self, sync):
        from repro.core.sync import faithful_sync, jacobi_sync, specmap_sync
        from repro.core.bitstream import MAX_UPM
        from repro.kernels.huffman.ops import make_decode_exits

        blobs, _ = _mixed_quality_batch()
        plan = build_batch_plan(blobs, chunk_bits=160, seq_chunks=4)
        dev = {k: jnp.asarray(v) for k, v in plan.device_arrays().items()}
        kernel_fn = make_decode_exits(
            s_max=plan.s_max, min_code_bits=plan.min_code_bits,
            chunk_bits=plan.chunk_bits, interpret=True)
        kw = dict(s_max=plan.s_max, min_code_bits=plan.min_code_bits)
        if sync == "jacobi":
            run = lambda fn: jacobi_sync(
                dev, max_rounds=plan.n_chunks + 2, decode_exits=fn, **kw)
        elif sync == "faithful":
            run = lambda fn: faithful_sync(
                dev, seq_chunks=plan.seq_chunks,
                max_outer=plan.n_sequences + 2, decode_exits=fn, **kw)
        else:
            run = lambda fn: specmap_sync(
                dev, max_upm=MAX_UPM, max_verify=plan.n_chunks + 2,
                decode_exits=fn, **kw)
        ref = run(None)           # pure-jnp default
        got = run(kernel_fn)      # Pallas kernel
        assert bool(ref.converged) and bool(got.converged)
        for a, b in zip(got.exits, ref.exits):
            assert np.array_equal(np.asarray(a), np.asarray(b))


class TestBackendKnob:
    def test_unknown_backend_fails_loudly(self):
        blobs, _ = _mixed_quality_batch()
        with pytest.raises(ValueError, match="unknown decode backend"):
            ParallelDecoder.from_bytes(blobs, backend="cuda")
        from repro.core.api import decode_batch
        with pytest.raises(ValueError, match="unknown decode backend"):
            decode_batch(blobs, backend="triton")

    def test_use_kernels_selects_pallas_end_to_end(self):
        """Regression: use_kernels=True used to swap only the IDCT and
        silently drop the Huffman kernel. The legacy flag still works but
        is deprecated — it must warn, pointing at backend=/fuse=."""
        blobs, exp = _mixed_quality_batch()
        with pytest.warns(DeprecationWarning, match="backend="):
            dec = ParallelDecoder.from_bytes(
                blobs, chunk_bits=160, use_kernels=True, interpret=True)
        assert dec.backend == "pallas"
        assert np.array_equal(np.asarray(dec.coefficients().coeffs), exp)

    def test_use_kernels_false_does_not_warn(self):
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            assert KB.resolve_backend(None, use_kernels=False) == "jnp"

    def test_resolve_backend(self):
        assert KB.resolve_backend(None) == "jnp"
        with pytest.warns(DeprecationWarning, match="backend="):
            assert KB.resolve_backend(None, use_kernels=True) == "pallas"
        assert KB.resolve_backend("pallas") == "pallas"
        with pytest.warns(DeprecationWarning, match="backend="):
            assert KB.resolve_backend("pallas", use_kernels=True) == "pallas"
        with pytest.raises(ValueError):
            KB.resolve_backend("mosaic")
        # conflicting legacy flag + explicit backend must not silently
        # drop the kernels (still warns on the legacy flag before raising)
        with pytest.warns(DeprecationWarning), \
                pytest.raises(ValueError, match="conflicting backend"):
            KB.resolve_backend("jnp", use_kernels=True)

    def test_interpret_resolution_order(self, monkeypatch):
        # explicit argument wins over everything
        monkeypatch.setenv(KB.INTERPRET_ENV, "0")
        assert KB.default_interpret(True) is True
        # env var beats the platform default
        assert KB.default_interpret(None) is False
        monkeypatch.setenv(KB.INTERPRET_ENV, "1")
        assert KB.default_interpret(None) is True
        monkeypatch.setenv(KB.INTERPRET_ENV, "yes")
        with pytest.raises(ValueError, match=KB.INTERPRET_ENV):
            KB.default_interpret(None)
        # platform default: interpret on CPU (test host), compiled off-CPU
        monkeypatch.delenv(KB.INTERPRET_ENV)
        import jax
        assert KB.default_interpret(None) is (jax.default_backend() == "cpu")
        # on an accelerator the env var may not force the interpreter
        monkeypatch.setattr(KB.jax, "default_backend", lambda: "tpu")
        assert KB.default_interpret(None) is False
        monkeypatch.setenv(KB.INTERPRET_ENV, "0")
        assert KB.default_interpret(None) is False
        monkeypatch.setenv(KB.INTERPRET_ENV, "1")
        with pytest.raises(ValueError, match="interpreter on 'tpu'"):
            KB.default_interpret(None)
        assert KB.default_interpret(True) is True


class TestColorKernel:
    @pytest.mark.parametrize("fh,fv", [(1, 1), (2, 1), (2, 2)])
    @pytest.mark.parametrize("shape", [(1, 16, 256), (2, 24, 300), (1, 8, 64)])
    def test_matches_ref(self, fh, fv, shape, rng):
        b, h, w = shape
        h = -(-h // (8 * fv)) * (8 * fv)
        w = -(-w // (8 * fh)) * (8 * fh)
        y = rng.uniform(0, 255, (b, h, w)).astype(np.float32)
        cb = rng.uniform(0, 255, (b, h // fv, w // fh)).astype(np.float32)
        cr_ = rng.uniform(0, 255, (b, h // fv, w // fh)).astype(np.float32)
        got = upsample_color(jnp.asarray(y), jnp.asarray(cb), jnp.asarray(cr_),
                             fh=fh, fv=fv)
        exp = upsample_color_ref(jnp.asarray(y), jnp.asarray(cb),
                                 jnp.asarray(cr_), fh, fv)
        # round-at-.5 may differ by 1 between scalar paths
        diff = np.abs(np.asarray(got).astype(int) - np.asarray(exp).astype(int))
        assert diff.max() <= 1
        assert (diff > 0).mean() < 0.01


class TestFuseParityMatrix:
    """Acceptance: every (schedule, fuse) cell of the Pallas backend is
    bit-identical — coefficients AND pixels — to backend="jnp" on a
    mixed-quality batch (the 8-device mesh variant of this matrix lives
    in tests/test_distribution.py)."""

    def _decode(self, blobs, sync, backend, fuse=None, **kw):
        dec = ParallelDecoder.from_bytes(
            blobs, chunk_bits=160, sync=sync, backend=backend, fuse=fuse,
            interpret=True, **kw)
        out = dec.decode("rgb")
        assert out.converged
        return dec, out

    @pytest.mark.parametrize("fuse", ["none", "post", "full"])
    @pytest.mark.parametrize(
        "sync", ["jacobi", "faithful", "specmap", "sequential"])
    def test_fused_bit_identical_to_jnp(self, sync, fuse):
        blobs, exp_coeffs = _mixed_quality_batch()
        _, ref = self._decode(blobs, sync, "jnp")
        dec, got = self._decode(blobs, sync, "pallas", fuse=fuse)
        assert np.array_equal(np.asarray(got.coeffs), exp_coeffs)
        assert np.array_equal(np.asarray(got.rgb), np.asarray(ref.rgb))
        if fuse == "post":
            # the megakernel replaced the unfused pixel chain: no
            # intermediate planes survive
            assert dec.program.pixels_fused
            assert got.planes is None
        if fuse == "full":
            # tiny batch, off-mesh: the in-kernel store must engage
            assert dec.program.store_fused

    def test_fused_bit_identical_unbucketed(self):
        """The bucket=False (exact-shape) cell of the matrix."""
        blobs, _ = _mixed_quality_batch()
        _, ref = self._decode(blobs, "jacobi", "jnp", bucket=False)
        _, got = self._decode(blobs, "jacobi", "pallas", fuse="post",
                              bucket=False)
        assert np.array_equal(np.asarray(got.rgb), np.asarray(ref.rgb))

    def test_fuse_requires_pallas_backend(self):
        blobs, _ = _mixed_quality_batch()
        with pytest.raises(ValueError, match="fuse"):
            ParallelDecoder.from_bytes(blobs, backend="jnp", fuse="post")
        with pytest.raises(ValueError, match="unknown fuse"):
            ParallelDecoder.from_bytes(blobs, backend="pallas",
                                       fuse="mega")


class TestAutotune:
    """The block-size autotuner: resolution order, loud validation, disk
    persistence, and the zero-recompile guarantee (tiles ride the
    DecodeProgram cache key, so a warm bucket never re-tunes/retraces)."""

    def _batch(self, seeds, quality=85):
        return [cr.encode_baseline(synth_image(48, 64, seed=s),
                                   quality=quality,
                                   subsampling="4:2:0").jpeg_bytes
                for s in seeds]

    def test_warm_bucket_zero_recompiles(self, monkeypatch, tmp_path):
        from repro.core import clear_decode_programs, decode_programs
        from repro.kernels import autotune as AT

        monkeypatch.delenv(AT.TILES_ENV, raising=False)
        monkeypatch.setenv(AT.TABLE_ENV, str(tmp_path / "tiles.json"))
        clear_decode_programs()
        AT.clear_tile_cache()
        for seeds in ((0, 1, 2), (7, 8, 9)):   # distinct, same bucket
            dec = ParallelDecoder.from_bytes(
                self._batch(seeds), chunk_bits=160, backend="pallas",
                fuse="post", interpret=True)
            assert dec.tiles is not None
            dec.decode("rgb")
        progs = [p for p in decode_programs() if p.backend == "pallas"]
        assert len(progs) == 1               # one bucket, one program
        assert progs[0].coeffs_traces == 1   # second batch: pure cache hit
        assert progs[0].pixels_traces == 1
        assert progs[0].tiles == AT.DEFAULT_TILES  # no measure => defaults

    def test_env_override_wins(self, monkeypatch):
        from repro.kernels import autotune as AT

        monkeypatch.setenv(AT.TILES_ENV, "exits=512,write=64,mcu=16")
        AT.clear_tile_cache()
        cfg = AT.autotune_tiles("any-bucket", "pallas", "post",
                                measure=lambda c: 0.0, kind="testdev")
        # override beats memo, table, and the measured search
        assert (cfg.exits_tile, cfg.write_tile, cfg.mcu_tile) == (512, 64, 16)
        assert cfg.unit_tile == AT.DEFAULT_TILES.unit_tile  # unnamed: default

    def test_bad_override_fails_loudly(self, monkeypatch):
        from repro.kernels import autotune as AT

        with pytest.raises(ValueError, match="multiple of 8"):
            AT.parse_tile_override("exits=7")
        with pytest.raises(ValueError, match="unknown"):
            AT.parse_tile_override("bogus=64")
        with pytest.raises(ValueError, match="key=value"):
            AT.parse_tile_override("128")
        with pytest.raises(ValueError, match="not an int"):
            AT.parse_tile_override("write=fast")
        with pytest.raises(ValueError, match="out of range"):
            AT.parse_tile_override("unit=0")
        # the end-to-end path surfaces the same error, not a fallback
        monkeypatch.setenv(AT.TILES_ENV, "exits=7")
        AT.clear_tile_cache()
        with pytest.raises(ValueError, match="multiple of 8"):
            ParallelDecoder.from_bytes(self._batch((0,)), backend="pallas",
                                       interpret=True)

    def test_measured_search_persists_to_table(self, tmp_path, monkeypatch):
        from repro.kernels import autotune as AT

        monkeypatch.delenv(AT.TILES_ENV, raising=False)
        monkeypatch.setenv(AT.TABLE_ENV, str(tmp_path / "tiles.json"))
        AT.clear_tile_cache()
        calls = []

        def measure(cfg):
            calls.append(cfg)
            return 0.0 if cfg.write_tile == 64 else 1.0

        won = AT.autotune_tiles("bucket-X", "pallas", "none",
                                measure=measure, kind="testdev")
        assert won.write_tile == 64
        assert len(calls) == len(AT.candidate_configs())
        # a fresh process (cleared memo) resolves the winner from disk
        # without re-measuring
        AT.clear_tile_cache()
        again = AT.autotune_tiles("bucket-X", "pallas", "none",
                                  kind="testdev")
        assert again == won
        # distinct tune keys don't collide
        other = AT.autotune_tiles("bucket-Y", "pallas", "none",
                                  kind="testdev")
        assert other == AT.DEFAULT_TILES
