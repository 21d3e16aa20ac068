"""repro.analysis: lint rule fixtures, baseline semantics, int32 contract
helpers, and jaxpr contract checks (taint analysis, donation, flip)."""
import dataclasses
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import contracts
from repro.analysis.lint import (Finding, apply_baseline, lint_paths,
                                 lint_source, load_baseline)

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src"


def lint(src, rules=None):
    return lint_source(textwrap.dedent(src), "repro/fixture.py", rules=rules)


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# Lint rule fixtures: each rule fires on its bad snippet, not on its good one
# ---------------------------------------------------------------------------

class TestTracedHostSync:
    def test_item_in_jit_fires(self):
        fs = lint("""
            import jax

            @jax.jit
            def f(x):
                n = x.sum().item()
                return n
        """)
        assert rules_of(fs) == ["traced-host-sync"]

    def test_int_cast_in_jit_fires(self):
        fs = lint("""
            import jax

            @jax.jit
            def f(x):
                return int(x.sum())
        """)
        assert rules_of(fs) == ["traced-host-sync"]

    def test_np_asarray_in_scan_body_fires(self):
        fs = lint("""
            import numpy as np
            from jax import lax

            def outer(xs):
                def body(c, x):
                    return c + np.asarray(x), None
                return lax.scan(body, 0.0, xs)
        """)
        assert rules_of(fs) == ["traced-host-sync"]

    def test_host_side_cast_clean(self):
        fs = lint("""
            def shape_of(arr):
                return int(arr.shape[0]), float(arr.dtype.itemsize)
        """)
        assert fs == []

    def test_constant_ish_in_jit_clean(self):
        fs = lint("""
            import jax

            @jax.jit
            def f(x):
                n = int(len(x))
                return x * n
        """)
        assert fs == []


class TestUnhashableStatic:
    def test_ndarray_field_on_frozen_dataclass_fires(self):
        fs = lint("""
            import dataclasses
            import numpy as np

            @dataclasses.dataclass(frozen=True)
            class Key:
                n: int
                arr: np.ndarray
        """)
        assert rules_of(fs) == ["unhashable-static"]
        assert "arr" in fs[0].message

    def test_eq_false_identity_hash_clean(self):
        fs = lint("""
            import dataclasses
            import numpy as np

            @dataclasses.dataclass(frozen=True, eq=False)
            class Spec:
                arr: np.ndarray
        """)
        assert fs == []

    def test_scalar_fields_clean(self):
        fs = lint("""
            import dataclasses

            @dataclasses.dataclass(frozen=True)
            class Key:
                n: int
                name: str
                dims: tuple
        """)
        assert fs == []

    def test_nested_jit_capture_fires(self):
        fs = lint("""
            import jax

            def build(table):
                @jax.jit
                def run(x):
                    return x + table
                return run
        """)
        assert rules_of(fs) == ["unhashable-static"]
        assert "table" in fs[0].message

    def test_module_level_jit_clean(self):
        fs = lint("""
            import jax

            SCALE = 2.0

            @jax.jit
            def run(x):
                return x * SCALE
        """)
        assert fs == []


class TestHostDivergence:
    def test_rendezvous_under_identity_branch_fires(self):
        fs = lint("""
            import jax

            def init():
                if jax.process_index() == 0:
                    jax.distributed.initialize()
        """)
        assert rules_of(fs) == ["host-divergence"]

    def test_early_return_before_rendezvous_fires(self):
        fs = lint("""
            def launch(client, rank):
                if rank != 0:
                    return None
                client.barrier("ready")
        """)
        assert rules_of(fs) == ["host-divergence"]

    def test_identity_branch_after_rendezvous_clean(self):
        fs = lint("""
            def launch(client, rank):
                client.barrier("ready")
                if rank == 0:
                    print("all hosts ready")
        """)
        assert fs == []


class TestSwallowedFormatError:
    def test_broad_except_fires(self):
        fs = lint("""
            def parse(blob):
                try:
                    return risky(blob)
                except Exception:
                    return None
        """)
        assert rules_of(fs) == ["swallowed-format-error"]

    def test_bare_except_fires(self):
        fs = lint("""
            def parse(blob):
                try:
                    return risky(blob)
                except:
                    return None
        """)
        assert rules_of(fs) == ["swallowed-format-error"]

    def test_reraise_clean(self):
        fs = lint("""
            def parse(blob):
                try:
                    return risky(blob)
                except Exception:
                    cleanup()
                    raise
        """)
        assert fs == []

    def test_validator_clean(self):
        fs = lint("""
            def validate_header(blob):
                try:
                    parse(blob)
                except Exception:
                    return False
                return True
        """)
        assert fs == []

    def test_narrow_except_clean(self):
        fs = lint("""
            def parse(blob):
                try:
                    return risky(blob)
                except (KeyError, ValueError):
                    return None
        """)
        assert fs == []


class TestF64Promotion:
    def test_jnp_dtype_kwarg_fires(self):
        fs = lint("""
            import jax.numpy as jnp

            def zeros(n):
                return jnp.zeros(n, dtype=jnp.float64)
        """)
        assert rules_of(fs) == ["f64-literal-promotion"]

    def test_astype_in_jit_fires(self):
        fs = lint("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                return x.astype(np.float64)
        """)
        assert rules_of(fs) == ["f64-literal-promotion"]

    def test_host_numpy_f64_clean(self):
        fs = lint("""
            import numpy as np

            def reference(n):
                return np.zeros(n, dtype=np.float64)
        """)
        assert fs == []

    def test_f32_clean(self):
        fs = lint("""
            import jax.numpy as jnp

            def zeros(n):
                return jnp.zeros(n, dtype=jnp.float32)
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# Suppression + baseline semantics
# ---------------------------------------------------------------------------

BAD_EXCEPT = """
    def parse(blob):
        try:
            return risky(blob)
        except Exception:{allow}
            return None
"""


class TestSuppression:
    def test_inline_allow_suppresses(self):
        fs = lint(BAD_EXCEPT.format(allow="  # repro: allow[swallowed-format-error]"))
        assert fs == []

    def test_allow_on_line_above_suppresses(self):
        fs = lint("""
            def parse(blob):
                try:
                    return risky(blob)
                # a justified catch-all  # repro: allow[swallowed-format-error]
                except Exception:
                    return None
        """)
        assert fs == []

    def test_allow_for_other_rule_does_not_suppress(self):
        fs = lint(BAD_EXCEPT.format(allow="  # repro: allow[traced-host-sync]"))
        assert rules_of(fs) == ["swallowed-format-error"]

    def test_allow_list_suppresses(self):
        fs = lint(BAD_EXCEPT.format(
            allow="  # repro: allow[traced-host-sync, swallowed-format-error]"))
        assert fs == []


class TestBaseline:
    def test_baselined_finding_filtered(self, tmp_path):
        fs = lint(BAD_EXCEPT.format(allow=""))
        assert len(fs) == 1
        bl = tmp_path / "baseline.txt"
        bl.write_text("# comment\n" + fs[0].baseline_key() + " :: known\n")
        new, stale = apply_baseline(fs, load_baseline(bl))
        assert new == [] and stale == []

    def test_stale_entry_reported(self, tmp_path):
        bl = tmp_path / "baseline.txt"
        bl.write_text("swallowed-format-error :: repro/gone.py :: except Exception: :: old\n")
        new, stale = apply_baseline([], load_baseline(bl))
        assert new == [] and len(stale) == 1

    def test_key_survives_line_drift(self):
        fs1 = lint(BAD_EXCEPT.format(allow=""))
        fs2 = lint("\n\n# moved down\n" + textwrap.dedent(BAD_EXCEPT.format(allow="")))
        assert fs1[0].line != fs2[0].line
        assert fs1[0].baseline_key() == fs2[0].baseline_key()


def test_repo_lint_clean_with_baseline():
    """The shipped baseline covers exactly the repo's current findings —
    no new findings, no stale entries."""
    findings = lint_paths([SRC / "repro"], root=SRC)
    assert not [f for f in findings if f.rule == "parse-error"]
    baseline = load_baseline(SRC / "repro" / "analysis" / "baseline.txt")
    new, stale = apply_baseline(findings, baseline)
    assert [f.format() for f in new] == []
    assert stale == []


def test_lint_cli_exits_zero():
    res = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "lint", "--baseline"],
        cwd=REPO, capture_output=True, text=True,
        env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin:/usr/local/bin"},
        timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr


# ---------------------------------------------------------------------------
# contracts: checked-int32 helpers and the index lattice
# ---------------------------------------------------------------------------

class TestCheckedInt32:
    def test_in_range_passes(self):
        assert contracts.checked_int32(contracts.INT32_MAX, "x") \
            == contracts.INT32_MAX

    def test_overflow_raises(self):
        with pytest.raises(contracts.ContractViolation):
            contracts.checked_int32(contracts.INT32_MAX + 1, "x")

    def test_violation_is_value_error(self):
        # runtime guards advertise ValueError; the shared helper must stay
        # catchable under the old contract
        assert issubclass(contracts.ContractViolation, ValueError)

    def test_coeff_capacity_guard(self):
        contracts.checked_coeff_capacity(1000)
        with pytest.raises(contracts.ContractViolation):
            contracts.checked_coeff_capacity(2**31 // 64)

    def test_coeff_capacity_overshoot_catches_more(self):
        tu = (2**31 - 100) // 64  # units_end fits, +overshoot does not
        contracts.checked_coeff_capacity(tu)
        with pytest.raises(contracts.ContractViolation):
            contracts.checked_coeff_capacity(tu, s_max=514)


def duck_shape(**kw):
    from types import SimpleNamespace
    base = dict(n_units=1 << 20, s_max=16, n_words=1 << 18, n_chunks=1 << 12,
                label=lambda: "duck")
    base.update(kw)
    return SimpleNamespace(**base)


class TestIndexLattice:
    def test_int_range_arithmetic(self):
        r = (contracts.IntRange(0, 10) + contracts.IntRange.const(5)) \
            * contracts.IntRange.const(64)
        assert (r.lo, r.hi) == (320, 960)
        assert r.fits_int32

    def test_small_shape_passes_both_models(self):
        sh = duck_shape()
        contracts.check_index_lattice(sh, model="valid")
        contracts.check_index_lattice(sh, model="adversarial")

    def test_huge_shape_fails_valid_model(self):
        with pytest.raises(contracts.ContractViolation):
            contracts.check_index_lattice(duck_shape(n_units=1 << 26),
                                          model="valid")

    def test_adversarial_strictly_tighter(self):
        # a shape the valid model admits but whose phantom damaged-segment
        # term overflows: the adversarial model must reject it
        sh = duck_shape(n_units=1 << 24, n_chunks=1 << 16, s_max=1024)
        contracts.check_index_lattice(sh, model="valid")
        with pytest.raises(contracts.ContractViolation):
            contracts.check_index_lattice(sh, model="adversarial")
        assert contracts.max_damaged_segment_chunks(sh) < sh.n_chunks

    def test_ranges_cover_named_indices(self):
        ranges = contracts.plan_index_ranges(duck_shape(), model="valid")
        for key in ("units_end", "write_index", "bit_position", "lane_index"):
            assert key in ranges, sorted(ranges)


def test_plan_shape_stays_hashable_frozen():
    """PlanShape keys the compiled-program cache: it must stay frozen and
    value-hashable (the unhashable-static lint class, as a runtime test)."""
    from repro.core.bitstream import PlanShape
    kw = dict(chunk_bits=1024, seq_chunks=32, s_max=4, min_code_bits=2,
              n_lanes=1, permuted=False, n_words=64, n_luts=1, n_tablesets=1,
              n_matrices=1, n_segments=1, n_chunks=4, n_sequences=1,
              n_units=16, n_images=1, uniform=True, geometry=None)
    a, b = PlanShape(**kw), PlanShape(**kw)
    assert a == b and hash(a) == hash(b) and len({a, b}) == 1
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.n_units = 17
    params = PlanShape.__dataclass_params__
    assert params.frozen and params.eq


# ---------------------------------------------------------------------------
# collectives accounting cross-check (unit level)
# ---------------------------------------------------------------------------

SYNTH_HLO = """
  %ag = f32[16,4]{1,0} all-gather(f32[8,4]{1,0} %p0), dimensions={0}
  %ars = f32[32]{0} all-reduce-start(f32[32]{0} %p1), to_apply=%add
  %ard = f32[32]{0} all-reduce-done(f32[32]{0} %ars)
  %dot = f32[8,8]{1,0} dot(f32[8,4]{1,0} %p0, f32[4,8]{1,0} %p2)
"""


def test_collective_counts_match_bytes_kinds():
    from repro.dist.collectives import collective_bytes, collective_counts
    counts = collective_counts(SYNTH_HLO)
    bytes_ = collective_bytes(SYNTH_HLO)
    assert counts == {"all-gather": 1, "all-reduce": 1}
    assert set(counts) == set(bytes_)
    assert all(bytes_[k] > 0 for k in counts)


# ---------------------------------------------------------------------------
# jaxpr contract checks on real decode programs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tier0_blobs():
    from repro.jpeg.encoder import DatasetSpec, build_dataset
    ds = build_dataset(DatasetSpec("analysis-t0", n_images=2, width=48,
                                   height=32, quality=75, restart_interval=2))
    return list(ds.jpeg_bytes)


def _decoder(blobs, **kw):
    from repro.core.api import ParallelDecoder
    return ParallelDecoder.from_bytes(list(blobs), **kw)


def _checked(dec, sync):
    from repro.analysis import jaxpr_check as J
    tr = J._trace(dec)
    names = J._invar_names(dec.data.words, dec._dev_rest)
    assert len(names) == len(tr.jaxpr.jaxpr.invars)
    return J, tr, names


@pytest.mark.parametrize("sync", ["jacobi", "faithful"])
def test_identity_program_clean(tier0_blobs, sync):
    dec = _decoder(tier0_blobs, sync=sync)
    J, tr, names = _checked(dec, sync)
    assert not dec.shape.permuted
    cell = "test-identity"
    assert J.check_lane_graph(tr.jaxpr, names, sync, False, cell) == []
    assert J.check_boundary(tr.jaxpr, names, cell) == []
    assert J.check_donation(tr, tr.jaxpr, cell) == []


def test_permuted_plan_flips_gather_contract(tier0_blobs):
    """The same checker that passes identity plans must find lane-graph
    indexed accesses on a permuted plan — proof it is not vacuous."""
    dec = _decoder(tier0_blobs, sync="jacobi", balance="roundrobin", lanes=2)
    J, tr, names = _checked(dec, "jacobi")
    assert dec.shape.permuted
    # permuted direction: tainted accesses exist, flip check passes
    assert J.check_lane_graph(tr.jaxpr, names, "jacobi", True, "flip") == []
    accesses = J.lane_graph_accesses(tr.jaxpr, names)
    assert any(a.taint for a in accesses)
    # and pretending the plan were identity must raise the violation
    vs = J.check_lane_graph(tr.jaxpr, names, "jacobi", False, "flip")
    assert vs and vs[0].contract == "identity-lane-graph"


def test_seeded_gather_is_caught(tier0_blobs):
    """Acceptance criterion: a deliberately injected lane-graph gather in
    an identity-plan lowering is detected."""
    from repro.analysis import jaxpr_check as J
    dec = _decoder(tier0_blobs, sync="jacobi")
    tr = J.seeded_gather_trace(dec)
    names = J._invar_names(dec.data.words, dec._dev_rest)
    vs = J.check_lane_graph(tr.jaxpr, names, "jacobi", False, "seeded")
    assert vs and vs[0].contract == "identity-lane-graph"
    assert "chunk_order" in vs[0].detail


def test_taint_tracks_through_loop_carry():
    """Fixpoint propagation: taint entering a loop carry on iteration one
    must be seen by an indexed access on iteration two."""
    import jax
    from jax import lax
    from repro.analysis import jaxpr_check as J

    def f(chunk_order, x):
        def body(_, carry):
            j, acc = carry
            return chunk_order[j], acc + x[j]
        return lax.fori_loop(0, 3, body, (0, 0.0))

    closed = jax.make_jaxpr(f)(np.zeros(4, np.int32), np.zeros(4, np.float32))
    accesses = J.lane_graph_accesses(closed, ["chunk_order", "x"])
    assert any("chunk_order" in a.taint for a in accesses)


def test_untainted_gather_not_flagged():
    import jax
    from repro.analysis import jaxpr_check as J

    def f(lut, idx, x):
        return x + lut[idx]

    closed = jax.make_jaxpr(f)(np.zeros(4, np.float32),
                               np.zeros((), np.int32),
                               np.zeros(4, np.float32))
    accesses = J.lane_graph_accesses(closed, ["lut", "idx", "x"])
    assert not any(a.taint for a in accesses)


def test_f64_scan_detects():
    import jax
    from repro.analysis import jaxpr_check as J
    with jax.enable_x64():
        j64 = jax.make_jaxpr(lambda x: x * 2.0)(np.float64(1.5))
    assert J.scan_f64(j64)
    j32 = jax.make_jaxpr(lambda x: x * 2.0)(np.float32(1.5))
    assert not J.scan_f64(j32)


def test_donation_lowering_regex():
    from repro.analysis.jaxpr_check import check_donation_lowering
    donor = ('func.func public @main(%arg0: tensor<172xui32> '
             '{jax.buffer_donor = true}, %arg1: tensor<6xi1>)')
    plain = ('func.func public @main(%arg0: tensor<172xui32>, '
             '%arg1: tensor<6xi1>)')
    assert check_donation_lowering(donor, "cell") == []
    vs = check_donation_lowering(plain, "cell")
    assert vs and vs[0].contract == "words-donated"


# ---------------------------------------------------------------------------
# unsafe-scatter-set lint fixtures
# ---------------------------------------------------------------------------

class TestUnsafeScatterSet:
    def test_dynamic_index_fires(self):
        fs = lint("""
            import jax.numpy as jnp

            def route(buf, idx, val):
                return buf.at[idx].set(val, mode="drop")
        """)
        assert "unsafe-scatter-set" in rules_of(fs)

    def test_computed_tuple_index_fires(self):
        fs = lint("""
            import jax.numpy as jnp

            def route(buf, rows, val):
                return buf.at[rows + 1, :].set(val)
        """)
        assert "unsafe-scatter-set" in rules_of(fs)

    def test_static_index_clean(self):
        fs = lint("""
            import jax.numpy as jnp

            def head(buf, val):
                a = buf.at[0].set(val)
                b = buf.at[1:4].set(val)
                return a, b.at[-1, :].set(val)
        """)
        assert "unsafe-scatter-set" not in rules_of(fs)

    def test_accumulating_add_clean(self):
        fs = lint("""
            import jax.numpy as jnp

            def hist(buf, idx):
                return buf.at[idx].add(1)
        """)
        assert "unsafe-scatter-set" not in rules_of(fs)

    def test_verified_module_exempt(self):
        from repro.analysis.lint import lint_source
        src = textwrap.dedent("""
            def write(out, tgt, val):
                return out.at[tgt].set(val, mode="drop")
        """)
        fs = lint_source(src, "repro/kernels/huffman/ops.py")
        assert "unsafe-scatter-set" not in rules_of(fs)
        fs = lint_source(src, "repro/core/somewhere.py")
        assert "unsafe-scatter-set" in rules_of(fs)

    def test_inline_allow_suppresses(self):
        fs = lint("""
            def write(out, tgt, val):
                # repro: allow[unsafe-scatter-set]
                return out.at[tgt].set(val, mode="drop")
        """)
        assert "unsafe-scatter-set" not in rules_of(fs)


# ---------------------------------------------------------------------------
# kernel verifier: lattice transfer functions, where-call rewrite, self-test
# ---------------------------------------------------------------------------

IR = contracts.IntRange


class TestKernelLatticeTransfers:
    def test_mod_signs(self):
        assert contracts.IntRange(-7, 7).mod(IR.const(5)) == IR(-4, 4)
        assert IR(0, 100).mod(IR.const(32)) == IR(0, 31)
        # the remainder never exceeds the dividend itself
        assert IR(0, 3).mod(IR.const(32)) == IR(0, 3)
        assert IR.const(-13).mod(IR.const(5)) == IR.const(-3)
        with pytest.raises(ValueError):
            IR(0, 4).mod(IR.const(0))

    def test_clamp_is_clip(self):
        assert IR(-5, 90).clamp(0, 63) == IR(0, 63)
        assert IR(10, 20).clamp(0, 63) == IR(10, 20)
        assert IR(-5, 90).clamp_min(IR.const(0)) == IR(0, 90)
        assert IR(-5, 90).clamp_max(IR.const(63)) == IR(-5, 63)

    def test_shift_and_mask(self):
        assert IR(0, 1054).shift_right(IR.const(5)) == IR(0, 32)
        assert IR(-64, 1054).shift_right(IR(0, 5)) == IR(-64, 1054)
        assert IR(-100, 3).bit_and_mask(0x1F) == IR(0, 31)
        assert IR(0, 7).bit_and_mask(0x1F) == IR(0, 7)
        with pytest.raises(ValueError):
            IR(0, 4).shift_right(IR(-1, 2))

    def test_join_meet_sub_scale(self):
        assert IR(0, 3).join(IR(10, 12)) == IR(0, 12)
        assert IR(0, 10).meet(IR(5, 99)) == IR(5, 10)
        with pytest.raises(ValueError):
            IR(0, 3).meet(IR(5, 9))
        assert IR(0, 10) - IR(2, 3) == IR(-3, 8)
        assert IR(-2, 3).scale(64) == IR(-128, 192)
        with pytest.raises(ValueError):
            IR(0, 1).scale(-1)

    def test_block_cover_grid_extremes(self):
        # exact cover passes
        contracts.check_block_cover(128, 32, IR(0, 3), "ok")
        # grid stops early: truncation
        with pytest.raises(contracts.ContractViolation):
            contracts.check_block_cover(128, 32, IR(0, 2), "short")
        # grid overruns the operand
        with pytest.raises(contracts.ContractViolation):
            contracts.check_block_cover(128, 32, IR(0, 4), "long")
        # first tile does not start at the origin
        with pytest.raises(contracts.ContractViolation):
            contracts.check_block_cover(128, 32, IR(1, 4), "offset")

    def test_tile_origin_range(self):
        assert contracts.tile_origin_range(IR(0, 3), 32) == IR(0, 96)


class TestKernelVerifier:
    def test_where_call_rewrites_to_callsite_select(self):
        """jnp.where lowers to a pjit of one *shared* body jaxpr; the
        verifier must resolve each call's select on its own call-site
        atoms, not the last call's (the alias-clobber class)."""
        import jax
        import jax.numpy as jnp
        from repro.analysis import kernel_check as kc

        def f(c, x, y):
            a = jnp.where(c, x, y)       # two calls sharing one body
            b = jnp.where(~c, y, x + 1)
            return a, b

        closed = jax.make_jaxpr(f)(np.zeros(4, bool),
                                   np.zeros(4, np.int32),
                                   np.zeros(4, np.int32))
        dm = kc.DefMap().build(closed.jaxpr)
        out_a, out_b = closed.jaxpr.outvars
        da, db = dm.rootdef(out_a), dm.rootdef(out_b)
        assert da is not None and da.primitive.name == "select_n"
        assert db is not None and db.primitive.name == "select_n"
        # call-site operands, not shared-body invars: a's cases are the
        # outer x/y vars themselves
        x_var, y_var = closed.jaxpr.invars[1], closed.jaxpr.invars[2]
        assert {dm.root(v) for v in da.invars[1:]} == {x_var, y_var}
        # b's true case is x + 1, a distinct expression
        assert any(
            (d := dm.rootdef(v)) is not None and d.primitive.name == "add"
            for v in db.invars[1:])

    def test_sentinel_split_sees_through_index_wrap(self):
        """.at[].set inserts a negative-index wrap select between the
        user's where(ok, tgt, N) and the scatter; the sentinel matcher
        must look through both it and the pjit wrapper."""
        import jax
        import jax.numpy as jnp
        from repro.analysis import kernel_check as kc

        def f(x, tgt, ok, val):
            idx = jnp.where(ok, tgt, x.shape[0])
            # repro: allow[unsafe-scatter-set] — fixture under test
            return x.at[idx].set(val, mode="drop", unique_indices=True)

        closed = jax.make_jaxpr(f)(
            np.zeros(8, np.int32), np.zeros(4, np.int32),
            np.zeros(4, bool), np.zeros(4, np.int32))
        dm = kc.DefMap().build(closed.jaxpr)
        scatter = [e for e in kc.iter_eqns(closed.jaxpr)
                   if e.primitive.name == "scatter"]
        assert scatter, "fixture did not lower to a scatter"
        split = kc._sentinel_split(dm, scatter[0].invars[1], 8)
        assert split is not None
        ok_atom, real_atom = split
        assert dm.root(real_atom) is closed.jaxpr.invars[1]
        assert dm.root(ok_atom) is closed.jaxpr.invars[2]

    @pytest.mark.slow
    def test_self_test_catches_all_three_seeds(self):
        """Acceptance criterion: the verifier flags an off-by-one pl.ds,
        a duplicate scatter index, and a non-covering BlockSpec."""
        from repro.analysis import kernel_check as kc
        assert kc.run_self_test() == []
