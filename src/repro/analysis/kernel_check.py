"""Kernel memory-safety verifier: static bounds, tiling and scatter-race
analysis for the Pallas decode path (``python -m repro.analysis kernels``).

The jaxpr contract checker (jaxpr_check.py) guards what the *compiler*
sees of whole decode programs; this module descends one layer further and
verifies the hand-written index arithmetic inside the Pallas kernels —
the layer where one colliding or out-of-bounds index silently corrupts
pixels instead of crashing. Three contract families
(``contracts.KERNEL_CHECK_FAMILIES``):

* **kernel-bounds** — every in-kernel ref access (``get`` / ``swap``,
  including ``pl.ds`` dynamic slices) and every
  unclamped gather index is proven in-bounds by abstract interpretation
  of the kernel jaxpr over the ``contracts.IntRange`` lattice. Loop
  carries go through a join-widen fixpoint with branch-guard refinement
  (``select_n`` whose predicate is a comparison clamps the refined
  operand) and affine trip-count widening for induction-style carries
  (the ``fori_loop`` counter, the symbol count ``n``). Documented
  operand intervals come from ``contracts.KERNEL_CONTRACTS`` — e.g. the
  LUT ``clen`` field is 5 bits wide but semantically <= 16 (JPEG B.1.1.5),
  which is exactly what proves the ``chunk_words + 2`` word window.
  Inside a kernel there is **no** clip/drop safety net, so every access
  must be proven; outside, gathers in CLIP/FILL_OR_DROP mode are safe by
  jnp semantics and only PROMISE_IN_BOUNDS accesses are checked.

* **kernel-scatter-race** — the write pass ends in one bulk
  ``out.at[tgt].set(val, mode="drop")`` whose claim to order-independence
  (docs/KERNELS.md) this module turns into a machine-checked proof:
  (1) per-lane stream positions strictly increase — the kernel jaxpr
  exhibits ``pos = n + run`` with carry update ``n' = n + run + 1`` and
  ``run >= 0`` (pattern-matched per symbol step, interval-checked);
  (2) per-lane output ranges are disjoint — segment coefficient bases
  are strictly non-overlapping (``bitstream.check_seg_coeff_disjoint``,
  verified on every tier-0 plan) and each lane is clamped into its
  segment (the ``ok`` mask carries both a lower and an upper bound);
  (3) masked entries go to the shared *past-the-end* sentinel, which is
  dropped by ``mode="drop"`` and therefore never writes — uniqueness is
  only required of indices that write. With all three established the
  scatter must declare ``unique_indices=True`` (XLA drops the sort — the
  free perf win); any *other* overwrite-scatter in a traced cell is
  flagged (use ``.add``, or the ``unsafe-scatter-set`` lint machinery).

* **kernel-tiling** — for every traced ``pallas_call``, each BlockSpec's
  ``index_map`` jaxpr is interval-evaluated over the whole grid range and
  ``tile origin = index_map(i) * tile`` must exactly cover the operand:
  no tile past the end, no silent remainder truncation, tile divides the
  dimension (``contracts.check_block_cover``). The bucket ladder's
  capacities are additionally checked tile-aligned and lane-block
  aligned (``n_chunks % n_lanes == 0``) so the shard_map pad-skip fast
  path in ``kernels/huffman/ops.py`` agrees with the ladder rungs.

Like the jaxpr checker, ``--self-test`` proves the machine catches what
it claims to catch before its green result is trusted: an off-by-one
``pl.ds`` store, a duplicate-index overwrite scatter, and a non-covering
BlockSpec are injected and all three must be flagged.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr, Var
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import contracts
from .contracts import IntRange

_BIG = 1 << 62  # "unbounded" endpoints for branch-constraint half-intervals


# ---------------------------------------------------------------------------
# Violations
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Violation:
    family: str   # KERNEL_CHECK_FAMILIES key
    cell: str     # which traced cell
    detail: str

    def format(self) -> str:
        return f"[{self.family}] {self.cell}: {self.detail}"


def _src(eqn) -> str:
    from jax._src import source_info_util
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return ""
    return f"{frame.file_name}:{frame.start_line}"


# ---------------------------------------------------------------------------
# Jaxpr walking utilities
# ---------------------------------------------------------------------------

def _open(j):
    """The Jaxpr of a ClosedJaxpr; anything else is returned as it is."""
    return j.jaxpr if isinstance(j, ClosedJaxpr) else j


def _subjaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, (ClosedJaxpr, Jaxpr)):
                yield _open(x)


def iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub)


def _is_var(x) -> bool:
    return isinstance(x, Var)


_CALL_PRIMS = ("jit", "closed_call", "core_call", "custom_jvp_call",
               "custom_vjp_call", "custom_vjp_call_jaxpr", "remat", "checkpoint")

#: Value-preserving (content-subset) prims the structural resolver and the
#: provenance tracker look straight through.
_PASSTHROUGH = ("broadcast_in_dim", "reshape", "squeeze", "copy",
                "convert_element_type", "slice", "stop_gradient", "transpose")


class _SynthPrim:
    __slots__ = ("name",)

    def __init__(self, name):
        self.name = name


_SELECT_N_P = _SynthPrim("select_n")


class _SynthEqn:
    """Call-site rewrite of a ``jnp.where`` pjit as a plain select_n eqn."""
    __slots__ = ("primitive", "invars", "outvars", "params", "source_info")

    def __init__(self, primitive, invars, outvars, source_info):
        self.primitive = primitive
        self.invars = invars
        self.outvars = outvars
        self.params = {}
        self.source_info = source_info


def _as_where_select(eqn):
    """Rewrite a pjit of jnp.where's ``_where`` helper as a synthetic
    select_n over the *call-site* atoms, or return None.

    jnp.where compiles every call in a trace to a pjit of one *shared*
    body jaxpr, so body-invar identity is ambiguous across call sites —
    any alias map keyed on body vars gets clobbered by the next call.
    The synthetic eqn keeps both structural matching and the guarded
    interval refinement call-site-local. Matched bodies contain exactly
    one select_n plus value-preserving wrappers, so the rewrite is exact.
    """
    if eqn.primitive.name not in _CALL_PRIMS or len(eqn.outvars) != 1:
        return None
    body = None
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in eqn.params:
            body = eqn.params[key]
            break
    if body is None:
        return None
    bj = _open(body)
    if len(bj.outvars) != 1 or bj.constvars \
            or len(bj.invars) != len(eqn.invars):
        return None
    sel, bdefs = None, {}
    for be in bj.eqns:
        for ov in be.outvars:
            bdefs[ov] = be
        if be.primitive.name == "select_n":
            if sel is not None or len(be.invars) != 3:
                return None
            sel = be
        elif not (be.primitive.name in _PASSTHROUGH
                  and len(be.invars) == 1):
            return None
    if sel is None:
        return None
    final = bj.outvars[0]
    for _ in range(8):  # outvar may sit behind trailing wrappers
        if final is sel.outvars[0]:
            break
        be = bdefs.get(final)
        if be is None or be is sel:
            return None
        final = be.invars[0]
    else:
        return None
    pos = {v: i for i, v in enumerate(bj.invars)}
    outer = []
    for a in sel.invars:
        for _ in range(8):
            if not _is_var(a) or a in pos:
                break
            be = bdefs.get(a)
            if be is None:
                return None
            a = be.invars[0]
        if _is_var(a):
            if a not in pos:
                return None
            a = eqn.invars[pos[a]]
        outer.append(a)
    return _SynthEqn(_SELECT_N_P, outer, list(eqn.outvars),
                     eqn.source_info)


class DefMap:
    """Definition-site map over a jaxpr *including* call-prim boundaries.

    ``alias`` records exact value equalities across pjit/call boundaries
    (body invar == outer atom; outer outvar == body outvar) so structural
    pattern matching sees through them. Other sub-jaxprs (scan bodies,
    index maps) get definitions but no carry aliasing — a scan carry is
    not equal to its initial value.
    """

    def __init__(self):
        self.defs: Dict[object, object] = {}
        self.alias: Dict[object, object] = {}

    def build(self, jaxpr) -> "DefMap":
        self._walk(jaxpr)
        return self

    def _walk(self, jaxpr):
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                self.defs[ov] = eqn
            if eqn.primitive.name in _CALL_PRIMS:
                synth = _as_where_select(eqn)
                if synth is not None:
                    # shared _where body: do NOT alias its invars (the
                    # next call site would clobber them) — define the
                    # outvar by the call-site select instead
                    self.defs[eqn.outvars[0]] = synth
                    continue
                body = None
                for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                    if key in eqn.params:
                        body = eqn.params[key]
                        break
                if body is not None:
                    bj = _open(body)
                    for bi, ai in zip(bj.invars, eqn.invars):
                        self.alias[bi] = ai
                    for ov, bv in zip(eqn.outvars, bj.outvars):
                        self.alias[ov] = bv
                    self._walk(bj)
                    continue
            if eqn.primitive.name == "scan":
                # scan consts ARE equal across the boundary (carries and
                # xs are not) — alias them so ref identity survives into
                # the loop body
                body = eqn.params["jaxpr"]
                bj = _open(body)
                nc = eqn.params["num_consts"]
                for bi, ai in zip(bj.invars[:nc], eqn.invars[:nc]):
                    self.alias[bi] = ai
                self._walk(bj)
                continue
            for sub in _subjaxprs(eqn.params):
                self._walk(sub)

    def root(self, atom, *, through=_PASSTHROUGH):
        """Follow aliases and value-preserving single-input eqns to the
        structural root of ``atom`` (a Var, Literal, or defining eqn's
        output left un-followed)."""
        seen = 0
        while seen < 200:
            seen += 1
            if not _is_var(atom):
                return atom
            if atom in self.alias:
                atom = self.alias[atom]
                continue
            eqn = self.defs.get(atom)
            if eqn is not None and eqn.primitive.name in through \
                    and len(eqn.invars) == 1:
                atom = eqn.invars[0]
                continue
            return atom
        return atom

    def rootdef(self, atom, *, through=_PASSTHROUGH):
        """The defining eqn of ``atom``'s structural root (or None)."""
        r = self.root(atom, through=through)
        return self.defs.get(r) if _is_var(r) else None

    def same_root(self, a, b) -> bool:
        ra, rb = self.root(a), self.root(b)
        if _is_var(ra) or _is_var(rb):
            return ra is rb
        va = getattr(ra, "val", ra)
        vb = getattr(rb, "val", rb)
        try:
            return bool(np.asarray(va).shape == np.asarray(vb).shape
                        and (np.asarray(va) == np.asarray(vb)).all())
        except Exception:
            return False

    def same_expr(self, a, b, depth: int = 2) -> bool:
        """Structural equality one level deeper than same_root: traced
        code has no CSE, so ``u + 1`` in a guard and ``u + 1`` in its
        branch are distinct add eqns over the same operands."""
        if self.same_root(a, b):
            return True
        if depth <= 0:
            return False
        da, db = self.rootdef(a), self.rootdef(b)
        if da is None or db is None or da.primitive is not db.primitive:
            return False
        if len(da.invars) != 2 or len(db.invars) != 2:
            return False
        (x1, y1), (x2, y2) = da.invars, db.invars
        straight = (self.same_expr(x1, x2, depth - 1)
                    and self.same_expr(y1, y2, depth - 1))
        if straight:
            return True
        if da.primitive.name in ("add", "mul", "max", "min", "and", "or"):
            return (self.same_expr(x1, y2, depth - 1)
                    and self.same_expr(y1, x2, depth - 1))
        return False

    def const_of(self, atom) -> Optional[int]:
        r = self.root(atom)
        if _is_var(r):
            eqn = self.defs.get(r)
            if eqn is not None and eqn.primitive.name == "iota":
                return None
            return None
        v = getattr(r, "val", None)
        if v is None:
            return None
        a = np.asarray(v)
        if a.dtype.kind not in "iub":
            return None
        if a.size == 1:
            return int(a.reshape(()))
        if a.size and (a == a.flat[0]).all():
            return int(a.flat[0])
        return None


# ---------------------------------------------------------------------------
# The interval interpreter
# ---------------------------------------------------------------------------

def _dtype_range(dtype) -> Optional[IntRange]:
    dt = np.dtype(dtype)
    if dt.kind == "b":
        return IntRange(0, 1)
    if dt.kind == "i":
        n = dt.itemsize * 8
        return IntRange(-(1 << (n - 1)), (1 << (n - 1)) - 1)
    if dt.kind == "u":
        return IntRange(0, (1 << (dt.itemsize * 8)) - 1)
    return None


@dataclasses.dataclass(frozen=True)
class AV:
    """Abstract value: interval (None for non-integer values) plus an
    optional provenance tag ``(operand role, accumulated right-shift)``
    used to recognize bit-packed table fields."""
    rng: Optional[IntRange] = None
    prov: Optional[Tuple[str, int]] = None

    def join(self, other: "AV") -> "AV":
        if self.rng is None or other.rng is None:
            rng = None
        else:
            rng = self.rng.join(other.rng)
        prov = self.prov if self.prov == other.prov else None
        return AV(rng, prov)


@dataclasses.dataclass
class RefInfo:
    role: str
    shape: Tuple[int, ...]
    contract: Optional[contracts.OperandContract]


def _fit(rng: Optional[IntRange], dtype) -> Optional[IntRange]:
    """Clamp to the dtype's representable range; wrap-around collapses to
    the full dtype range (sound, maximally imprecise)."""
    dr = _dtype_range(dtype)
    if dr is None or rng is None:
        return dr
    if dr.contains(rng):
        return rng
    return dr


class KernelInterp:
    """Interval abstract interpretation over one Pallas kernel jaxpr."""

    MAX_JOIN_ROUNDS = 12

    def __init__(self, cell: str, params: Dict[str, int],
                 operand_contracts: Sequence, dm: DefMap):
        self.cell = cell
        self.params = params
        self.contracts = list(operand_contracts)
        self.dm = dm
        self.env: Dict[object, AV] = {}
        self.refs: Dict[object, RefInfo] = {}
        self.violations: List[Violation] = []
        self.check = False

    # -- environment ------------------------------------------------------

    def get(self, atom) -> AV:
        if not _is_var(atom):
            val = getattr(atom, "val", None)
            a = np.asarray(val)
            if a.dtype.kind in "iub" and a.size:
                return AV(IntRange(int(a.min()), int(a.max())))
            return AV(None)
        if atom in self.env:
            return self.env[atom]
        aval = getattr(atom, "aval", None)
        dt = getattr(aval, "dtype", None)
        return AV(_dtype_range(dt) if dt is not None else None)

    def bind(self, var, av: AV):
        self.env[var] = av

    def _ref_of(self, atom) -> Optional[RefInfo]:
        r = self.dm.root(atom, through=())
        return self.refs.get(r) if _is_var(r) else None

    def _flag(self, family, detail, eqn=None):
        if not self.check:
            return
        where = _src(eqn) if eqn is not None else ""
        if where:
            detail = f"{detail} ({where})"
        self.violations.append(Violation(family, self.cell, detail))

    # -- contract lookups -------------------------------------------------

    def _content_av(self, ref: RefInfo, col: Optional[int]) -> AV:
        c = ref.contract
        dflt = AV(IntRange(contracts.INT32_MIN, contracts.INT32_MAX))
        if c is None:
            return dflt
        rng = None
        if col is not None and col in c.ranges:
            rng = c.ranges[col]
        elif None in c.ranges:
            rng = c.ranges[None]
        if rng is not None:
            lo, hi = rng(self.params)
            return AV(IntRange(int(lo), int(hi)))
        prov = (ref.role, 0) if c.fields else None
        return AV(dflt.rng, prov)

    def _field_range(self, prov, mask: int) -> Optional[IntRange]:
        role, shift = prov
        for oc in self.contracts:
            if oc is not None and oc.role == role:
                for f in oc.fields:
                    if f.shift == shift and f.mask == mask:
                        return IntRange(f.lo, f.hi)
        return None

    # -- main loop --------------------------------------------------------

    def run_jaxpr(self, jaxpr, in_avs: Sequence):
        """Bind invars (AV or RefInfo) and interpret every eqn."""
        for var, v in zip(jaxpr.invars, in_avs):
            if isinstance(v, RefInfo):
                self.refs[var] = v
            else:
                self.bind(var, v)
        for cv in jaxpr.constvars:
            self.bind(cv, AV(None))
        for eqn in jaxpr.eqns:
            self.eval_eqn(eqn)
        return [self.get(o) for o in jaxpr.outvars]

    def eval_eqn(self, eqn):
        name = eqn.primitive.name
        fn = getattr(self, f"_p_{name}", None)
        if fn is not None:
            fn(eqn)
            return
        if name in _CALL_PRIMS:
            self._call(eqn)
            return
        if name in _PASSTHROUGH and len(eqn.invars) == 1:
            src = self.get(eqn.invars[0])
            for ov in eqn.outvars:
                self.bind(ov, AV(_fit(src.rng, ov.aval.dtype), src.prov))
            return
        for ov in eqn.outvars:
            dt = getattr(ov.aval, "dtype", None)
            self.bind(ov, AV(_dtype_range(dt) if dt is not None else None))

    def _p_jit(self, eqn):
        synth = _as_where_select(eqn)
        if synth is not None:
            # evaluate jnp.where at the call boundary so the guarded
            # refinement sees call-site atoms (the shared body's invars
            # have no stable identity across call sites)
            self._p_select_n(synth)
            return
        self._call(eqn)

    def _call(self, eqn):
        body = None
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            if key in eqn.params:
                body = eqn.params[key]
                break
        if body is None:
            for ov in eqn.outvars:
                self.bind(ov, AV(None))
            return
        bj = _open(body)
        ins = []
        for a in eqn.invars:
            ri = self._ref_of(a)
            ins.append(ri if ri is not None else self.get(a))
        outs = self.run_jaxpr(bj, ins)
        for ov, av in zip(eqn.outvars, outs):
            self.bind(ov, av)

    # -- integer arithmetic ----------------------------------------------

    def _int2(self, eqn):
        a, b = (self.get(x) for x in eqn.invars)
        return a, b, eqn.outvars[0]

    def _bind_fit(self, ov, rng, prov=None):
        self.bind(ov, AV(_fit(rng, ov.aval.dtype), prov))

    def _p_add(self, eqn):
        a, b, ov = self._int2(eqn)
        rng = a.rng + b.rng if (a.rng and b.rng) else None
        self._bind_fit(ov, rng)

    def _p_sub(self, eqn):
        a, b, ov = self._int2(eqn)
        rng = a.rng - b.rng if (a.rng and b.rng) else None
        self._bind_fit(ov, rng)

    def _p_mul(self, eqn):
        a, b, ov = self._int2(eqn)
        rng = a.rng * b.rng if (a.rng and b.rng) else None
        self._bind_fit(ov, rng)

    def _p_max(self, eqn):
        a, b, ov = self._int2(eqn)
        rng = a.rng.clamp_min(b.rng) if (a.rng and b.rng) else None
        self._bind_fit(ov, rng)

    def _p_min(self, eqn):
        a, b, ov = self._int2(eqn)
        rng = a.rng.clamp_max(b.rng) if (a.rng and b.rng) else None
        self._bind_fit(ov, rng)

    def _p_rem(self, eqn):
        a, b, ov = self._int2(eqn)
        rng = None
        if a.rng and b.rng and not (b.rng.lo <= 0 <= b.rng.hi):
            rng = a.rng.mod(b.rng)
        self._bind_fit(ov, rng)

    def _p_clamp(self, eqn):
        lo, x, hi = (self.get(v) for v in eqn.invars)
        rng = None
        if x.rng and lo.rng and hi.rng:
            rng = x.rng.clamp_min(lo.rng).clamp_max(hi.rng)
        self._bind_fit(ov := eqn.outvars[0], rng)

    def _p_dynamic_slice(self, eqn):
        # a dynamic slice's contents are a subset of its source's
        # contents whatever the start indices, so the *value* interval
        # passes through — but not the identity/provenance (the slice
        # position is data-dependent), hence interpreter-only and NOT in
        # _PASSTHROUGH. Needed for per-lane scalar reads like `idx[l]`
        # in the fused-store kernel's fori_loop.
        src = self.get(eqn.invars[0])
        ov = eqn.outvars[0]
        self.bind(ov, AV(_fit(src.rng, ov.aval.dtype)))

    def _p_and(self, eqn):
        a_atom, b_atom = eqn.invars
        a, b = self.get(a_atom), self.get(b_atom)
        ov = eqn.outvars[0]
        if np.dtype(ov.aval.dtype).kind == "b":
            self.bind(ov, AV(IntRange(0, 1)))
            return
        rng, prov = None, None
        ca = self.dm.const_of(a_atom)
        cb = self.dm.const_of(b_atom)
        mask, src_av = (cb, a) if cb is not None and cb >= 0 else \
                       (ca, b) if ca is not None and ca >= 0 else (None, None)
        if mask is not None:
            rng = (src_av.rng or IntRange(-_BIG, _BIG)).bit_and_mask(mask)
            if src_av.prov is not None:
                fr = self._field_range(src_av.prov, mask)
                if fr is not None:
                    rng = rng.meet(fr) if rng else fr
        elif a.rng and b.rng and a.rng.lo >= 0 and b.rng.lo >= 0:
            rng = IntRange(0, min(a.rng.hi, b.rng.hi))
        self._bind_fit(ov, rng, prov)

    def _p_or(self, eqn):
        a, b, ov = self._int2(eqn)
        if np.dtype(ov.aval.dtype).kind == "b":
            self.bind(ov, AV(IntRange(0, 1)))
            return
        rng = None
        if a.rng and b.rng and a.rng.lo >= 0 and b.rng.lo >= 0:
            cover = 1
            while cover - 1 < max(a.rng.hi, b.rng.hi):
                cover <<= 1
            rng = IntRange(0, cover - 1)
        self._bind_fit(ov, rng)

    _p_xor = _p_or

    def _p_not(self, eqn):
        ov = eqn.outvars[0]
        if np.dtype(ov.aval.dtype).kind == "b":
            self.bind(ov, AV(IntRange(0, 1)))
        else:
            self.bind(ov, AV(_dtype_range(ov.aval.dtype)))

    def _p_shift_left(self, eqn):
        a_atom, s_atom = eqn.invars
        a, s = self.get(a_atom), self.get(s_atom)
        ov = eqn.outvars[0]
        rng = None
        if a.rng and s.rng and a.rng.lo >= 0 and s.rng.lo >= 0 \
                and s.rng.hi < 64:
            rng = IntRange(a.rng.lo << s.rng.lo, a.rng.hi << s.rng.hi)
        self._bind_fit(ov, rng)

    def _shift_right(self, eqn, *, logical):
        a_atom, s_atom = eqn.invars
        a, s = self.get(a_atom), self.get(s_atom)
        ov = eqn.outvars[0]
        cs = self.dm.const_of(s_atom)
        rng, prov = None, None
        if a.rng is not None and s.rng is not None and s.rng.lo >= 0:
            if logical and a.rng.lo < 0:
                dr = _dtype_range(ov.aval.dtype)
                hi = (dr.hi if dr else (1 << 32) - 1) >> s.rng.lo
                rng = IntRange(0, hi)
            else:
                rng = a.rng.shift_right(s.rng)
        if a.prov is not None and cs is not None:
            prov = (a.prov[0], a.prov[1] + cs)
        self._bind_fit(ov, rng, prov)

    def _p_shift_right_logical(self, eqn):
        self._shift_right(eqn, logical=True)

    def _p_shift_right_arithmetic(self, eqn):
        self._shift_right(eqn, logical=False)

    def _p_convert_element_type(self, eqn):
        src = self.get(eqn.invars[0])
        ov = eqn.outvars[0]
        self._bind_fit(ov, src.rng, src.prov)

    def _p_iota(self, eqn):
        ov = eqn.outvars[0]
        dim = eqn.params.get("dimension", 0)
        n = ov.aval.shape[dim] if ov.aval.shape else 1
        self.bind(ov, AV(IntRange(0, max(0, n - 1))))

    def _p_concatenate(self, eqn):
        av = self.get(eqn.invars[0])
        for x in eqn.invars[1:]:
            av = av.join(self.get(x))
        self.bind(eqn.outvars[0], av)

    def _p_pad(self, eqn):
        self.bind(eqn.outvars[0],
                  self.get(eqn.invars[0]).join(self.get(eqn.invars[1])))

    def _cmp(self, eqn):
        self.bind(eqn.outvars[0], AV(IntRange(0, 1)))

    _p_lt = _p_le = _p_gt = _p_ge = _p_eq = _p_ne = _cmp

    # -- guarded select ---------------------------------------------------

    _CMP_PRIMS = {"lt", "le", "gt", "ge", "eq"}

    def _branch_bound(self, prim: str, true_branch: bool,
                      other_rng: IntRange, lhs: bool) -> Optional[IntRange]:
        """Constraint interval for one comparison operand on one branch.

        ``lhs`` selects which operand is being constrained: for
        ``lt(a, b)`` the lhs constraint bounds ``a`` given ``b``'s range,
        the rhs constraint bounds ``b`` given ``a``'s.
        """
        if not lhs:
            flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
                    "eq": "eq"}
            return self._branch_bound(flip[prim], true_branch, other_rng,
                                      lhs=True)
        if prim == "eq":
            return other_rng if true_branch else None
        if prim == "lt":
            return IntRange(-_BIG, other_rng.hi - 1) if true_branch \
                else IntRange(other_rng.lo, _BIG)
        if prim == "le":
            return IntRange(-_BIG, other_rng.hi) if true_branch \
                else IntRange(other_rng.lo + 1, _BIG)
        if prim == "gt":
            return IntRange(other_rng.lo + 1, _BIG) if true_branch \
                else IntRange(-_BIG, other_rng.hi)
        if prim == "ge":
            return IntRange(other_rng.lo, _BIG) if true_branch \
                else IntRange(-_BIG, other_rng.hi - 1)
        return None

    def _refine_case(self, case_atom, cmp_eqn, true_branch: bool,
                     fallback: AV) -> Optional[AV]:
        """Tighten a select case's interval using the branch condition.

        Handles ``case == cmp_operand`` and ``case == cmp_operand + d``;
        returns None when the branch is infeasible (constraint disjoint
        from the operand's interval — that case contributes nothing).
        """
        a_atom, b_atom = cmp_eqn.invars
        prim = cmp_eqn.primitive.name
        for operand, other, lhs in ((a_atom, b_atom, True),
                                    (b_atom, a_atom, False)):
            other_rng = self.get(other).rng
            op_rng = self.get(operand).rng
            if other_rng is None or op_rng is None:
                continue
            bound = self._branch_bound(prim, true_branch, other_rng, lhs)
            if bound is None:
                continue
            if self.dm.same_expr(case_atom, operand):
                try:
                    return AV(op_rng.meet(bound))
                except ValueError:
                    return None
            d = self.dm.rootdef(case_atom)
            if d is not None and d.primitive.name == "add":
                x, y = d.invars
                for u, v in ((x, y), (y, x)):
                    if self.dm.same_expr(u, operand):
                        vr = self.get(v).rng
                        if vr is None:
                            continue
                        try:
                            return AV(op_rng.meet(bound) + vr)
                        except ValueError:
                            return None
        return fallback

    def _p_select_n(self, eqn):
        pred = eqn.invars[0]
        cases = eqn.invars[1:]
        ov = eqn.outvars[0]
        avs: List[Optional[AV]] = [self.get(c) for c in cases]
        cmp_eqn = self.dm.rootdef(pred)
        if cmp_eqn is not None and cmp_eqn.primitive.name in self._CMP_PRIMS \
                and len(cases) == 2:
            avs = [
                self._refine_case(cases[0], cmp_eqn, False, avs[0]),
                self._refine_case(cases[1], cmp_eqn, True, avs[1]),
            ]
        live = [a for a in avs if a is not None]
        if not live:
            live = [AV(_dtype_range(ov.aval.dtype))]
        out = live[0]
        for a in live[1:]:
            out = out.join(a)
        self._bind_fit(ov, out.rng, out.prov)

    # -- ref accesses -----------------------------------------------------

    @staticmethod
    def _unflatten_indexers(tree, leaves):
        return jax.tree_util.tree_unflatten(tree, list(leaves))

    def _indexer_parts(self, eqn):
        """(ref_atom, indexers, value_atom|None) for get/swap — the two
        primitives ref indexing (``x = ref[idx]``, ``ref[idx] = v``)
        lowers to."""
        name = eqn.primitive.name
        if name == "get":
            idx = self._unflatten_indexers(eqn.params["tree"], eqn.invars[1:])
            return eqn.invars[0], idx, None
        if name == "swap":
            idx = self._unflatten_indexers(eqn.params["tree"], eqn.invars[2:])
            return eqn.invars[0], idx, eqn.invars[1]
        raise AssertionError(name)

    def _check_dim(self, what: str, dim: int, idx_rng: IntRange,
                   extent: int, eqn):
        """idx + extent-1 must stay below dim; idx must be non-negative."""
        if idx_rng.lo < 0 or idx_rng.hi + extent - 1 > dim - 1:
            self._flag(
                "kernel-bounds",
                f"{what}: index range [{idx_rng.lo}, "
                f"{idx_rng.hi + extent - 1}] exceeds dimension {dim}",
                eqn)

    def _check_indexers(self, role: str, shape, indexers, eqn):
        for nd in indexers:
            dims = list(shape)
            for d, ix in enumerate(getattr(nd, "indices", ())):
                if d >= len(dims):
                    break
                dim = dims[d]
                if hasattr(ix, "start") and hasattr(ix, "size"):  # Slice
                    start, size = ix.start, ix.size
                    stride = getattr(ix, "stride", 1) or 1
                    if isinstance(start, int):
                        rng = IntRange.const(start)
                    else:
                        rng = self.get(start).rng
                    if rng is None:
                        self._flag("kernel-bounds",
                                   f"{role}[dim {d}]: dynamic slice start "
                                   f"has no provable bound", eqn)
                        continue
                    self._check_dim(f"{role}[dim {d}] pl.ds", dim, rng,
                                    (size - 1) * stride + 1, eqn)
                elif isinstance(ix, int):
                    self._check_dim(f"{role}[dim {d}]", dim,
                                    IntRange.const(ix), 1, eqn)
                else:  # dynamic scalar or integer array index
                    rng = self.get(ix).rng
                    if rng is None:
                        self._flag("kernel-bounds",
                                   f"{role}[dim {d}]: index has no "
                                   f"provable bound", eqn)
                        continue
                    self._check_dim(f"{role}[dim {d}]", dim, rng, 1, eqn)

    def _static_last_col(self, indexers) -> Optional[int]:
        for nd in indexers:
            idx = getattr(nd, "indices", ())
            if not idx:
                continue
            last = idx[-1]
            if isinstance(last, int):
                return last
            if hasattr(last, "start") and getattr(last, "size", None) == 1 \
                    and isinstance(last.start, int):
                return last.start
            c = self.dm.const_of(last) if _is_var(last) or hasattr(
                last, "val") else None
            if c is not None:
                return c
        return None

    def _p_get(self, eqn):
        ref, indexers, _ = self._indexer_parts(eqn)
        ri = self._ref_of(ref)
        role = ri.role if ri else "ref"
        if ri is not None:
            self._check_indexers(role, ri.shape, indexers, eqn)
            av = self._content_av(ri, self._static_last_col(indexers))
        else:
            av = AV(None)
        for ov in eqn.outvars:
            dt = getattr(ov.aval, "dtype", None)
            rng = _fit(av.rng, dt) if dt is not None else None
            self.bind(ov, AV(rng, av.prov))

    def _p_swap(self, eqn):
        ref, indexers, _val = self._indexer_parts(eqn)
        ri = self._ref_of(ref)
        if ri is not None:
            self._check_indexers(ri.role, ri.shape, indexers, eqn)
            av = self._content_av(ri, self._static_last_col(indexers))
        else:
            av = AV(None)
        for ov in eqn.outvars:
            self.bind(ov, av)

    def _p_gather(self, eqn):
        """In-kernel jnp advanced indexing — no clip net in Mosaic, so the
        per-component index intervals must be proven."""
        operand, indices = eqn.invars[:2]
        ov = eqn.outvars[0]
        dnums = eqn.params["dimension_numbers"]
        op_shape = operand.aval.shape
        slice_sizes = eqn.params["slice_sizes"]
        comp_avs = self._gather_component_avs(indices, len(dnums.start_index_map))
        for k, od in enumerate(dnums.start_index_map):
            rng = comp_avs[k].rng if k < len(comp_avs) else None
            extent = slice_sizes[od]
            if rng is None:
                self._flag("kernel-bounds",
                           f"gather[dim {od}]: index has no provable bound",
                           eqn)
                continue
            self._check_dim(f"gather[dim {od}]", op_shape[od], rng, extent,
                            eqn)
        src = self.get(operand)
        self.bind(ov, AV(_fit(src.rng, ov.aval.dtype), src.prov))

    def _gather_component_avs(self, indices_atom, n_components) -> List[AV]:
        """Per-component intervals of a gather index operand: looks through
        the concatenate that jnp advanced indexing builds so each indexed
        dimension keeps its own bound."""
        d = self.dm.rootdef(indices_atom)
        if d is not None and d.primitive.name == "concatenate" \
                and len(d.invars) == n_components:
            return [self.get(x) for x in d.invars]
        return [self.get(indices_atom)] * n_components

    # -- scan (fori_loop) -------------------------------------------------

    def _p_scan(self, eqn):
        p = eqn.params
        nc, ncar = p["num_consts"], p["num_carry"]
        length = p["length"]
        body = p["jaxpr"]
        bj = _open(body)

        const_ins = []
        for a in eqn.invars[:nc]:
            ri = self._ref_of(a)
            const_ins.append(ri if ri is not None else self.get(a))
        init_avs = [self.get(a) for a in eqn.invars[nc:nc + ncar]]
        xs_avs = [self.get(a) for a in eqn.invars[nc + ncar:]]

        def run(carry, check):
            prev = self.check
            self.check = check
            try:
                outs = self.run_jaxpr(bj, const_ins + list(carry) + xs_avs)
            finally:
                self.check = prev
            return outs[:ncar], outs[ncar:]

        carry = list(init_avs)
        stable = False
        for _ in range(self.MAX_JOIN_ROUNDS):
            outs, _ys = run(carry, check=False)
            new = [c.join(o) for c, o in zip(carry, outs)]
            if all(self._av_covers(c, o) for c, o in zip(carry, outs)):
                stable = True
                break
            carry = new

        final_out = [None] * ncar
        if not stable:
            outs, _ys = run(carry, check=False)
            for j in range(ncar):
                if self._av_covers(carry[j], outs[j]):
                    continue
                widened = self._affine_widen(run, carry, init_avs[j], j,
                                             length)
                if widened is None:
                    self._flag(
                        "kernel-bounds",
                        f"scan carry {j} cannot be bounded (neither a "
                        f"join fixpoint nor an affine induction bound)",
                        eqn)
                    carry[j] = AV(_dtype_range(bj.invars[nc + j].aval.dtype))
                else:
                    carry[j], final_out[j] = widened

        # final pass with settled carry-in intervals: record bound checks
        outs, ys = run(carry, check=True)
        for ov, av in zip(eqn.outvars[:ncar],
                          [f or o for f, o in zip(final_out, outs)]):
            self.bind(ov, av)
        for ov, av in zip(eqn.outvars[ncar:], ys):
            self.bind(ov, av)

    @staticmethod
    def _av_covers(a: AV, b: AV) -> bool:
        if a.rng is None:
            return True
        if b.rng is None:
            return False
        return a.rng.contains(b.rng)

    def _affine_widen(self, run, carry, init: AV, j: int, length: int):
        """Trip-count widening for induction-style carries: if the carry's
        transfer is ``c -> c + [k_lo, k_hi]`` (independent of c), then over
        L iterations the in-body value is ``init + (L-1) * step`` and the
        carry-out is ``init + L * step``."""
        if init.rng is None:
            return None
        probes = []
        for base in (0, 1 << 20):
            c2 = list(carry)
            c2[j] = AV(IntRange.const(base))
            outs, _ = run(c2, check=False)
            if outs[j].rng is None:
                return None
            probes.append((base, outs[j].rng))
        (b0, r0), (b1, r1) = probes
        if r1.lo - r0.lo != b1 - b0 or r1.hi - r0.hi != b1 - b0:
            return None
        step = IntRange(r0.lo - b0, r0.hi - b0)
        lo_s, hi_s = min(0, step.lo), max(0, step.hi)
        in_body = IntRange(init.rng.lo + (length - 1) * lo_s,
                           init.rng.hi + (length - 1) * hi_s)
        out = IntRange(init.rng.lo + length * lo_s,
                       init.rng.hi + length * hi_s)
        return AV(in_body), AV(out)


# ---------------------------------------------------------------------------
# Per-pallas_call checks
# ---------------------------------------------------------------------------

def find_pallas_calls(closed_jaxpr) -> List:
    return [e for e in iter_eqns(closed_jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]


def _index_map_ranges(bm, grid) -> List[IntRange]:
    """Interval-evaluate one BlockSpec index_map jaxpr over the grid."""
    imj = bm.index_map_jaxpr
    jx = _open(imj)
    dm = DefMap().build(jx)
    interp = KernelInterp("index_map", {}, [], dm)
    in_avs = [AV(IntRange(0, max(0, g - 1))) for g in grid]
    outs = interp.run_jaxpr(jx, in_avs[:len(jx.invars)])
    return [o.rng if o.rng is not None else IntRange(0, 0) for o in outs]


def check_tiling(pc_eqn, cell: str) -> List[Violation]:
    out: List[Violation] = []
    gm = pc_eqn.params["grid_mapping"]
    grid = tuple(gm.grid)
    for bm in gm.block_mappings:
        shape = tuple(bm.array_aval.shape)
        block = tuple(d.block_size if isinstance(d, pl.Blocked) else None
                      for d in bm.block_shape)
        try:
            idx_ranges = _index_map_ranges(bm, grid)
        except Exception as e:  # pragma: no cover - diagnostic path
            out.append(Violation(
                "kernel-tiling", cell,
                f"{bm.origin}: index_map could not be evaluated: {e}"))
            continue
        if len(idx_ranges) != len(block):
            out.append(Violation(
                "kernel-tiling", cell,
                f"{bm.origin}: index_map arity {len(idx_ranges)} != "
                f"block rank {len(block)}"))
            continue
        for d, (dim, tile, br) in enumerate(zip(shape, block, idx_ranges)):
            if tile is None:
                continue  # squeezed/element dims carry no tile here
            try:
                contracts.check_block_cover(
                    dim, tile, br, f"{bm.origin} dim {d}")
            except contracts.ContractViolation as e:
                out.append(Violation("kernel-tiling", cell, str(e)))
    return out


def check_kernel_bounds(pc_eqn, cell: str, contract, params: Dict[str, int]):
    """Bounds family over one pallas_call's kernel jaxpr. Returns
    (violations, interp) — the interp is reused by the scatter prover."""
    gm = pc_eqn.params["grid_mapping"]
    kj = pc_eqn.params["jaxpr"]
    bj = _open(kj)
    dm = DefMap().build(bj)
    operand_contracts = list(contract.operands) if contract else []
    interp = KernelInterp(cell, params, operand_contracts, dm)

    ins: List[object] = []
    n_in = gm.num_inputs
    for i, var in enumerate(bj.invars):
        shape = tuple(getattr(var.aval, "shape", ()) or ())
        if i < n_in:
            oc = operand_contracts[i] if i < len(operand_contracts) else None
            role = oc.role if oc else f"in{i}"
        else:
            oc, role = None, f"out{i - n_in}"
        ins.append(RefInfo(role, shape, oc))
    interp.check = True
    interp.run_jaxpr(bj, ins)
    return interp.violations, interp, dm, bj


# ---------------------------------------------------------------------------
# Scatter-race: structural proof of the write-pass scatter
# ---------------------------------------------------------------------------

#: Source files whose overwrite-scatters carry a structural proof below.
_SCATTER_SITES = {
    "repro/kernels/huffman/ops.py": "write-pass-pallas",
    "repro/core/decode.py": "write-pass-jnp",
}


def _site_of(eqn) -> Optional[str]:
    s = _src(eqn)
    for suffix, name in _SCATTER_SITES.items():
        if suffix.split("/")[-1] in s and suffix.rsplit("/", 2)[-2] in s:
            return name
    return None


def _and_leaves(dm: DefMap, atom, depth=0):
    """Comparison leaves of a boolean and-chain (through not/broadcast)."""
    if depth > 16:
        return
    d = dm.rootdef(atom)
    if d is None:
        return
    name = d.primitive.name
    if name == "and":
        for x in d.invars:
            yield from _and_leaves(dm, x, depth + 1)
    elif name == "not":
        yield ("not", d)
    elif name in ("lt", "le", "gt", "ge", "eq", "ne"):
        yield (name, d)


def _unwrap_negative_index_select(dm: DefMap, atom):
    """Look through the ``where(i < 0, i + dim, i)`` wrap jnp inserts on
    dynamic indices. Value-preserving for non-negative indices, and the
    sentinel (== dim >= 0) passes through unchanged, so descending to the
    unwrapped index is sound for the structural checks."""
    for _ in range(4):
        d = dm.rootdef(atom)
        if d is None or d.primitive.name != "select_n" \
                or len(d.invars) != 3:
            return atom
        pred, case_f, case_t = d.invars
        cmp = dm.rootdef(pred)
        if cmp is None or cmp.primitive.name != "lt" \
                or dm.const_of(cmp.invars[1]) != 0:
            return atom
        x = cmp.invars[0]
        matched = None
        for plain, wrapped in ((case_f, case_t), (case_t, case_f)):
            if not dm.same_root(plain, x):
                continue
            add = dm.rootdef(wrapped)
            if add is not None and add.primitive.name == "add" and any(
                    dm.same_root(s, x) for s in add.invars):
                matched = plain
                break
        if matched is None:
            return atom
        atom = matched
    return atom


def _sentinel_split(dm: DefMap, indices_atom, out_dim: int):
    """Match ``where(ok, real, past_the_end)`` (either case order).

    Returns (ok_atom, real_atom) or None."""
    indices_atom = _unwrap_negative_index_select(dm, indices_atom)
    d = dm.rootdef(indices_atom)
    if d is None or d.primitive.name != "select_n" or len(d.invars) != 3:
        return None
    pred, case_f, case_t = d.invars
    for sentinel, real in ((case_f, case_t), (case_t, case_f)):
        c = dm.const_of(sentinel)
        if c is not None and c >= out_dim:
            return pred, real
    return None


def prove_stream_monotone(interp: KernelInterp, dm: DefMap, bj,
                          pos_ref_var) -> Tuple[bool, str]:
    """Per-lane monotonicity of the write kernel's pos stream.

    Looks for the store ``pos = where(rec, n + run, -1)`` inside the
    symbol scan, with the matching carry update ``n' = n + (run + 1)``
    on the recording branch and ``run >= 0`` — together these make each
    lane's recorded positions strictly increasing.
    """
    for eqn in iter_eqns(bj):
        if eqn.primitive.name != "swap":
            continue
        ref, val = eqn.invars[0], eqn.invars[1]
        if dm.root(ref, through=()) is not pos_ref_var:
            continue
        sel = dm.rootdef(val)
        if sel is None or sel.primitive.name != "select_n" \
                or len(sel.invars) != 3:
            return False, "pos store is not a guarded select"
        _pred, case_f, case_t = sel.invars
        pos_expr = None
        for neg, pos_case in ((case_f, case_t), (case_t, case_f)):
            if dm.const_of(neg) == -1:
                pos_expr = pos_case
        if pos_expr is None:
            return False, "pos store has no -1 masked branch"
        add = dm.rootdef(pos_expr)
        if add is None or add.primitive.name != "add":
            return False, "recorded pos is not n + run"
        x, y = add.invars
        for n_atom, run_atom in ((x, y), (y, x)):
            run_rng = interp.get(dm.root(run_atom)).rng
            if run_rng is not None and run_rng.lo < 0:
                continue
            if self_increment_matches(dm, bj, n_atom, run_atom):
                if run_rng is None:
                    return False, "run term has no provable interval"
                return True, ""
        return False, ("no carry found with n' = n + run + 1 matching "
                       "the stored n + run (run >= 0)")
    return False, "no store to the pos stream found in the kernel"


def self_increment_matches(dm: DefMap, bj, n_atom, run_atom) -> bool:
    """Does some scan carry update ``n_atom`` as ``n + (run_atom + 1)``
    on its taken branch?"""
    for eqn in iter_eqns(bj):
        if eqn.primitive.name != "scan":
            continue
        body = eqn.params["jaxpr"]
        sub = _open(body)
        n_root = dm.root(n_atom)
        for ov in sub.outvars:
            sel = dm.rootdef(ov)
            if sel is None or sel.primitive.name != "select_n" \
                    or len(sel.invars) != 3:
                continue
            _pred, case_f, case_t = sel.invars
            for stay, adv in ((case_f, case_t), (case_t, case_f)):
                if not dm.same_root(stay, n_atom):
                    continue
                add = dm.rootdef(adv)
                if add is None or add.primitive.name != "add":
                    continue
                a, b = add.invars
                for base, step in ((a, b), (b, a)):
                    if dm.root(base) is not n_root:
                        continue
                    sadd = dm.rootdef(step)
                    if sadd is None or sadd.primitive.name != "add":
                        continue
                    u, v = sadd.invars
                    for r, one in ((u, v), (v, u)):
                        if dm.same_root(r, run_atom) \
                                and dm.const_of(one) == 1:
                            return True
    return False


def check_scatters(cell: str, closed_jaxpr, proven_kernels: Dict,
                   dm: DefMap) -> List[Violation]:
    """The scatter-race family over one traced cell."""
    out: List[Violation] = []
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "scatter":
            continue
        site = _site_of(eqn)
        src = _src(eqn) or "unknown source"
        if site is None:
            out.append(Violation(
                "kernel-scatter-race", cell,
                f"overwrite scatter at {src} has no distinctness proof — "
                f"use .at[...].add, or register a proof site"))
            continue
        if not eqn.params.get("unique_indices", False):
            out.append(Violation(
                "kernel-scatter-race", cell,
                f"{site} scatter at {src} is proven duplicate-free but "
                f"does not declare unique_indices=True"))
        operand, indices = eqn.invars[0], eqn.invars[1]
        out_dim = int(operand.aval.shape[0])
        split = _sentinel_split(dm, indices, out_dim)
        if split is None:
            out.append(Violation(
                "kernel-scatter-race", cell,
                f"{site} scatter at {src}: masked targets are not routed "
                f"to a past-the-end sentinel via where(ok, tgt, N)"))
            continue
        ok_atom, real_atom = split
        leaves = {name for name, _ in _and_leaves(dm, ok_atom)}
        if not leaves & {"le", "lt"}:
            out.append(Violation(
                "kernel-scatter-race", cell,
                f"{site} scatter at {src}: ok mask has no upper clamp "
                f"comparison (idx <= write_max)"))
        if site == "write-pass-pallas":
            if not leaves & {"ge", "gt"}:
                out.append(Violation(
                    "kernel-scatter-race", cell,
                    f"{site} scatter at {src}: ok mask has no pos >= 0 "
                    f"guard"))
            if not _real_from_proven_stream(dm, real_atom, proven_kernels):
                out.append(Violation(
                    "kernel-scatter-race", cell,
                    f"{site} scatter at {src}: target stream does not "
                    f"trace back to a kernel with a proven monotone pos "
                    f"stream"))
    return out


def _real_from_proven_stream(dm: DefMap, real_atom, proven_kernels) -> bool:
    """Does the in-bounds target expression ``write_base + pos`` take its
    ``pos`` from a pallas_call output whose kernel passed the
    monotonicity proof?"""
    d = dm.rootdef(real_atom)
    if d is None or d.primitive.name != "add":
        return False
    for side in d.invars:
        r = dm.root(side)
        if not _is_var(r):
            continue
        src_eqn = dm.defs.get(r)
        if src_eqn is None or src_eqn.primitive.name != "pallas_call":
            continue
        pos_index = proven_kernels.get(id(src_eqn))
        if pos_index is None:
            continue
        if src_eqn.outvars.index(r) == pos_index:
            return True
    return False


# ---------------------------------------------------------------------------
# Tier-0 cells: trace the real kernels at the tier-0 grid's shapes
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def tier0_cells():
    """(name, BatchPlan) pairs mirroring the jaxpr checker's tier-0 grid.

    The restart cell frames with small chunks so segments span several
    lanes (multi-chunk write bases, non-trivial seg_coeff_base); the
    plain cell uses the default 1024-bit framing."""
    from ..core.bitstream import build_batch_plan
    from ..jpeg.encoder import DatasetSpec, build_dataset
    ds_rst = build_dataset(DatasetSpec("t0-restart", n_images=2, width=48,
                                       height=32, quality=75,
                                       restart_interval=2))
    ds_one = build_dataset(DatasetSpec("t0-plain", n_images=1, width=64,
                                       height=64, quality=90))
    return [
        ("t0-restart", build_batch_plan(list(ds_rst.jpeg_bytes),
                                        chunk_bits=128)),
        ("t0-plain", build_batch_plan(list(ds_one.jpeg_bytes),
                                      chunk_bits=1024)),
    ]


def _huffman_params(plan_like, max_upm: int, n_luts: int) -> Dict[str, int]:
    return dict(chunk_bits=plan_like.chunk_bits, s_max=plan_like.s_max,
                max_upm=max_upm, n_luts=n_luts)


def _huffman_args(n_words: int, n_luts: int, c: int, max_upm: int):
    i32 = jnp.int32
    return (
        _sds((n_words,), jnp.uint32),
        _sds((n_luts, 65536), i32),
        _sds((c, max_upm, 2), i32),
    ) + tuple(_sds((c,), i32) for _ in range(7))


def _check_one_pallas_call(pc, cell: str, contract, params,
                           proven: Dict) -> List[Violation]:
    """Tiling + bounds on one pallas_call; write kernels additionally get
    the pos-stream monotonicity proof (recorded in ``proven``)."""
    out = check_tiling(pc, cell)
    vs, interp, dm, bj = check_kernel_bounds(pc, cell, contract, params)
    out += vs
    gm = pc.params["grid_mapping"]
    n_out = len(bj.invars) - gm.num_inputs
    if contract is not None and n_out == 3:  # the write kernel: (out, pos, val)
        pos_ref = bj.invars[gm.num_inputs + 1]
        ok, why = prove_stream_monotone(interp, dm, bj, pos_ref)
        if ok:
            proven[id(pc)] = 1  # pos is pallas_call output 1
        else:
            out.append(Violation(
                "kernel-scatter-race", cell,
                f"write-kernel pos stream not provably monotone: {why}"))
    return out


def _check_cell(cell: str, closed, contract, params,
                scatter: bool = False, expect_kernels: int = 1):
    """All families over one traced cell's closed jaxpr."""
    out: List[Violation] = []
    proven: Dict = {}
    pcs = find_pallas_calls(closed)
    if len(pcs) < expect_kernels:
        out.append(Violation(
            "kernel-bounds", cell,
            f"expected >= {expect_kernels} pallas_call(s) in the trace, "
            f"found {len(pcs)} — the verifier lost sight of the kernel"))
    for pc in pcs:
        out += _check_one_pallas_call(pc, cell, contract, params, proven)
    if scatter:
        dm = DefMap().build(closed.jaxpr)
        out += check_scatters(cell, closed, proven, dm)
    return out


def check_plan_cells(name: str, plan, verbose: bool = False):
    """Trace and verify every kernel the decode path runs for one plan."""
    import functools

    from ..core import decode as D
    from ..core.bitstream import plan_shape
    from ..core.state import DecodeState
    from ..kernels.autotune import TILE_CANDIDATES
    from ..kernels.fused import ops as FOPS
    from ..kernels.fused.pixels import fused_pixels_pallas
    from ..kernels.huffman import ops as HOPS
    from ..kernels.huffman.huffman import decode_exits_pallas
    from ..kernels.idct.idct import fused_idct

    out: List[Violation] = []
    n_cells = 0
    i32 = jnp.int32
    c = plan.n_chunks
    max_upm = plan.unit_lut_row.shape[1]
    n_luts = plan.luts.shape[0]
    kw = dict(s_max=plan.s_max, min_code_bits=plan.min_code_bits,
              chunk_words=plan.chunk_bits // 32, interpret=True)
    params = _huffman_params(plan, max_upm, n_luts)
    contracts_ = contracts.KERNEL_CONTRACTS

    # -- host invariant the scatter proof consumes ------------------------
    from ..core import bitstream as B
    try:
        B.check_seg_coeff_disjoint(plan.seg_coeff_base, plan.total_units,
                                   what=f"plan {name}")
    except Exception as e:
        out.append(Violation("kernel-scatter-race", name, str(e)))

    # -- exits kernel at actual and at bucketed capacities ----------------
    # Every autotune lane-tile candidate gets its own cell: the tuner may
    # pick any of them per device, so bounds + tiling must hold for all,
    # not just the winner.
    for tag, nw, nc, sm, cb in (
        ("", len(plan.words), c, plan.s_max, plan.chunk_bits),
        (":bucketed", None, None, None, None),
    ):
        if tag:
            sh = plan_shape(plan)
            nw, nc, sm, cb = sh.n_words, sh.n_chunks, sh.s_max, sh.chunk_bits
            kw2 = dict(s_max=sm, min_code_bits=sh.min_code_bits,
                       chunk_words=cb // 32, interpret=True)
            p2 = dict(params, chunk_bits=cb, s_max=sm)
        else:
            kw2, p2 = kw, params
        for et in TILE_CANDIDATES["exits_tile"]:
            cell = f"huffman-exits@{name}{tag}:t{et}"
            closed = jax.make_jaxpr(
                functools.partial(decode_exits_pallas, tile=et, **kw2))(
                    *_huffman_args(nw, n_luts, nc, max_upm))
            out += _check_cell(cell, closed, contracts_["huffman-exits"], p2)
            n_cells += 1
            if verbose:
                print(f"checked {cell}")

    # -- write pass: kernel + the bulk scatter, in one trace --------------
    dev = {k: _sds(v.shape, v.dtype) for k, v in plan.device_arrays().items()}
    n_coef = plan.total_units * 64

    def write_cell(dev, p, out_buf, wb, wm, *, tile):
        z = jnp.zeros_like(p)
        entry = DecodeState(p, z, z, z)
        return HOPS.decode_coeffs(
            dev, entry, out=out_buf, write_base=wb, write_max=wm,
            s_max=plan.s_max, min_code_bits=plan.min_code_bits,
            chunk_bits=plan.chunk_bits, tile=tile, interpret=True)

    stream_race_ok = True
    for wt in TILE_CANDIDATES["write_tile"]:
        cell = f"write-pass@{name}:t{wt}"
        closed = jax.make_jaxpr(functools.partial(write_cell, tile=wt))(
            dev, _sds((c,), i32), _sds((n_coef,), i32),
            _sds((c,), i32), _sds((c,), i32))
        vs = _check_cell(cell, closed, contracts_["huffman-write"], params,
                         scatter=True)
        stream_race_ok &= not any(
            v.family == "kernel-scatter-race" for v in vs)
        out += vs
        n_cells += 1
        if verbose:
            print(f"checked {cell}")

    # -- fuse="full" in-kernel store --------------------------------------
    # Race-freedom of the in-kernel store is accepted by *reduction*: it
    # replays the stream kernel's per-symbol recurrence (_symbol_step),
    # whose pos stream the cells above prove monotone, and serializes the
    # writes (sequential grid + fori_loop). The reduction is only sound
    # while the stream proof holds — if it broke, every store cell fails.
    def store_cell(dev, p, out_buf, wb, wm, *, tile):
        z = jnp.zeros_like(p)
        entry = DecodeState(p, z, z, z)
        return FOPS.decode_coeffs_full(
            dev, entry, out=out_buf, write_base=wb, write_max=wm,
            s_max=plan.s_max, min_code_bits=plan.min_code_bits,
            chunk_bits=plan.chunk_bits, tile=tile, interpret=True)

    p_store = dict(params, n_coef=n_coef)
    for wt in TILE_CANDIDATES["write_tile"]:
        cell = f"write-store@{name}:t{wt}"
        closed = jax.make_jaxpr(functools.partial(store_cell, tile=wt))(
            dev, _sds((c,), i32), _sds((n_coef,), i32),
            _sds((c,), i32), _sds((c,), i32))
        out += _check_cell(cell, closed, contracts_["huffman-write-store"],
                           p_store)
        if not stream_race_ok:
            out.append(Violation(
                "kernel-scatter-race", cell,
                "the in-kernel coefficient store is accepted by reduction "
                "to the stream write kernel's monotone-pos proof, which "
                "FAILED for this plan — the store's race-freedom is "
                "unproven"))
        n_cells += 1
        if verbose:
            print(f"checked {cell}")

    # -- the jnp write pass shares the scatter contract -------------------
    def jnp_write_cell(dev, p, out_buf, wb, wm):
        m = D.chunk_meta(dev)
        z = jnp.zeros_like(p)
        entry = DecodeState(p, z, z, z)
        return D.decode_span(
            dev, entry, m["word_base"], m["limit"], m["ts"], m["upm"],
            s_max=plan.s_max, min_code_bits=plan.min_code_bits,
            write=True, out=out_buf, write_base=wb, write_max=wm)

    cell = f"write-pass-jnp@{name}"
    closed = jax.make_jaxpr(jnp_write_cell)(
        dev, _sds((c,), i32), _sds((n_coef,), i32),
        _sds((c,), i32), _sds((c,), i32))
    out += _check_cell(cell, closed, None, {}, scatter=True,
                       expect_kernels=0)
    n_cells += 1
    if verbose:
        print(f"checked {cell}")

    # -- fused IDCT -------------------------------------------------------
    cell = f"idct@{name}"
    nq = plan.m_matrices.shape[0]
    closed = jax.make_jaxpr(
        functools.partial(fused_idct, interpret=True))(
            _sds((plan.total_units, 64), i32),
            _sds((nq, 64, 64), jnp.float32),
            _sds((plan.total_units,), i32))
    out += _check_cell(cell, closed, contracts_["idct"], {})
    n_cells += 1
    if verbose:
        print(f"checked {cell}")

    # -- fused pixel megakernel (fuse="post"|"full"), per MCU-tile --------
    sh = plan_shape(plan)
    g = sh.geometry
    if sh.uniform and g is not None and FOPS.pixels_fusible(g):
        upm = g.units_per_mcu
        n_mcus = plan.total_units // upm
        f32 = jnp.float32
        for mt in TILE_CANDIDATES["mcu_tile"]:
            cell = f"fused-pixels@{name}:t{mt}"
            closed = jax.make_jaxpr(functools.partial(
                fused_pixels_pallas, comp_h=tuple(g.comp_h),
                comp_v=tuple(g.comp_v), h_max=g.h_max, v_max=g.v_max,
                upm=upm, tile=mt, interpret=True))(
                    _sds((n_mcus * upm, 64), i32),
                    _sds((nq, 64, 64), f32),
                    _sds((n_mcus * upm,), i32))
            out += _check_cell(cell, closed, contracts_["fused-pixels"], {})
            n_cells += 1
            if verbose:
                print(f"checked {cell}")

    # -- bucket-ladder / pad-skip alignment -------------------------------
    out += check_ladder_alignment(name, sh)
    return out, n_cells


def check_color_cells(verbose: bool = False):
    """The color kernel's tiling contract at both subsampling layouts."""
    import functools

    from ..kernels.color.color import upsample_color

    out: List[Violation] = []
    n_cells = 0
    f32 = jnp.float32
    for fh, fv, h, w in ((1, 1, 8, 256), (2, 2, 16, 256)):
        cell = f"color@f{fh}{fv}"
        closed = jax.make_jaxpr(
            functools.partial(upsample_color, fh=fh, fv=fv, interpret=True))(
                _sds((1, h, w), f32),
                _sds((1, h // fv, w // fh), f32),
                _sds((1, h // fv, w // fh), f32))
        out += _check_cell(cell, closed,
                           contracts.KERNEL_CONTRACTS["color"], {})
        n_cells += 1
        if verbose:
            print(f"checked {cell}")
    return out, n_cells


def check_ladder_alignment(name: str, shape) -> List[Violation]:
    """The tiling contract's host half: bucket-ladder capacities stay
    tile-aligned, and the shard_map pad-skip fast path (ops._run skips
    padding when the lane capacity divides the mesh) agrees with the
    ladder — a bucketed plan's lane capacity is n_lanes equal blocks."""
    from ..core.bitstream import bucket_capacity
    from ..kernels.autotune import TILE_CANDIDATES
    from ..kernels.huffman.huffman import TILE_C, WRITE_TILE_C, _tile_for

    out: List[Violation] = []
    if shape.n_chunks % shape.n_lanes:
        out.append(Violation(
            "kernel-tiling", name,
            f"bucketed lane capacity {shape.n_chunks} is not a multiple "
            f"of n_lanes {shape.n_lanes}: the shard_map pad-skip fast "
            f"path would re-pad every batch"))
    # every lane-tile cap the autotuner may pick, plus the defaults
    caps = sorted({TILE_C, WRITE_TILE_C}
                  | set(TILE_CANDIDATES["exits_tile"])
                  | set(TILE_CANDIDATES["write_tile"]))
    rung = 1
    while rung <= shape.n_chunks:
        for cap in caps:
            tile = _tile_for(rung, cap)
            pad = (-rung) % tile
            if (rung + pad) % tile:
                out.append(Violation(
                    "kernel-tiling", name,
                    f"ladder rung {rung}: lane tile {tile} does not "
                    f"divide padded capacity {rung + pad}"))
        rung = bucket_capacity(rung + 1)
    return out


# ---------------------------------------------------------------------------
# Seeded-violation self-test
# ---------------------------------------------------------------------------

def run_self_test(verbose: bool = False) -> List[str]:
    """Prove the verifier catches what it claims to catch: an off-by-one
    pl.ds, a duplicated scatter index, a non-covering BlockSpec, and a
    misaligned fused-pixels tile must each be flagged by their family."""
    import functools

    failures: List[str] = []

    # 1. off-by-one pl.ds: rows [1, 8] into an 8-row operand
    def bad_kernel(x_ref, o_ref):
        def body(i, acc):
            v = x_ref[pl.ds(i + 1, 1), :]
            return acc + jnp.sum(v)
        o_ref[0, 0] = jax.lax.fori_loop(0, 8, body, jnp.float32(0.0))

    fn = pl.pallas_call(
        bad_kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=True)
    closed = jax.make_jaxpr(fn)(_sds((8, 4), jnp.float32))
    vs = _check_cell("self-test:oob-ds", closed, None, {})
    if not any(v.family == "kernel-bounds" for v in vs):
        failures.append("seeded off-by-one pl.ds not caught by "
                        "kernel-bounds")
    elif verbose:
        print(f"self-test oob-ds caught: {vs[0].detail}")

    # 2. duplicated scatter index with an overwrite .set
    def dup_scatter(x):
        idx = jnp.zeros((4,), jnp.int32)
        # repro: allow[unsafe-scatter-set] — deliberately unsafe seed
        return x.at[idx].set(jnp.arange(4, dtype=x.dtype), mode="drop",
                             unique_indices=True)

    closed = jax.make_jaxpr(dup_scatter)(_sds((8,), jnp.int32))
    vs = check_scatters("self-test:dup-scatter", closed, {},
                        DefMap().build(closed.jaxpr))
    if not any(v.family == "kernel-scatter-race" for v in vs):
        failures.append("seeded duplicate-index scatter not caught by "
                        "kernel-scatter-race")
    elif verbose:
        print(f"self-test dup-scatter caught: {vs[0].detail}")

    # 3. non-covering BlockSpec: 2 tiles x 4 cover 8 of 10 elements
    def ident(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    fn = pl.pallas_call(
        ident,
        grid=(2,),
        in_specs=[pl.BlockSpec((4,), lambda i: (i,))],
        out_specs=pl.BlockSpec((4,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((10,), jnp.float32),
        interpret=True)
    closed = jax.make_jaxpr(fn)(_sds((10,), jnp.float32))
    vs = _check_cell("self-test:truncating-blockspec", closed, None, {})
    if not any(v.family == "kernel-tiling" for v in vs):
        failures.append("seeded non-covering BlockSpec not caught by "
                        "kernel-tiling")
    elif verbose:
        print(f"self-test truncating-blockspec caught: {vs[0].detail}")

    # 4. misaligned fused-pixels MCU tile: the real megakernel launched
    # with a grid that covers only 8 of 10 MCUs (tile_m=4, grid=(2,)) —
    # exactly the bug a bad autotune candidate would introduce if the
    # fused cells' tiling contract were not enforced
    from ..kernels.fused.pixels import _pixels_kernel

    upm, tile_m = 6, 4  # 4:2:0 layout: comp (2,1,1)x(2,1,1), h_max=v_max=2
    fn = pl.pallas_call(
        functools.partial(_pixels_kernel, nq=1, upm=upm,
                          comp_h=(2, 1, 1), comp_v=(2, 1, 1),
                          h_max=2, v_max=2, tile_m=tile_m),
        grid=(2,),
        in_specs=[
            pl.BlockSpec((tile_m * upm, 64), lambda i: (i, 0)),
            pl.BlockSpec((tile_m * upm, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 64, 64), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_m, 3, 16, 16), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((10, 3, 16, 16), jnp.float32),
        interpret=True)
    closed = jax.make_jaxpr(fn)(
        _sds((10 * upm, 64), jnp.float32), _sds((10 * upm, 1), jnp.int32),
        _sds((1, 64, 64), jnp.float32))
    vs = _check_cell("self-test:fused-tile-misalign", closed, None, {})
    if not any(v.family == "kernel-tiling" for v in vs):
        failures.append("seeded fused-cell tile misalignment not caught "
                        "by kernel-tiling")
    elif verbose:
        print(f"self-test fused-tile-misalign caught: {vs[0].detail}")

    return failures


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def run(self_test: bool = False, verbose: bool = False) -> int:
    violations: List[Violation] = []
    n_cells = 0
    for name, plan in tier0_cells():
        vs, n = check_plan_cells(name, plan, verbose=verbose)
        violations += vs
        n_cells += n
    vs, n = check_color_cells(verbose=verbose)
    violations += vs
    n_cells += n

    if self_test:
        failures = run_self_test(verbose=verbose)
        for f in failures:
            violations.append(Violation("self-test", "seeded", f))
        if not failures:
            print("self-test: all 4 seeded violations caught (off-by-one "
                  "pl.ds, duplicate scatter index, non-covering BlockSpec, "
                  "fused-cell tile misalignment)")

    for v in violations:
        print(v.format())
    print(f"{len(violations)} kernel-contract violation"
          f"{'s' if len(violations) != 1 else ''} across {n_cells} cells "
          f"(families: {', '.join(contracts.KERNEL_CHECK_FAMILIES)})")
    return 1 if violations else 0
