"""Contract checker over the *traced* decode programs.

Where the AST linter reads source, this walks the jaxprs the decode
pipeline actually stages: for a tier-0 grid of PlanShapes x 4 sync
schedules x 2 backends it traces ``DecodeProgram.coeffs_fn`` and checks
the contracts declared in :mod:`repro.analysis.contracts`:

* **identity-lane-graph** — the PR 3 "gather creep" regression. A naive
  "identity programs contain zero gather primitives" is false (LUT
  lookups and segment-axis gathers are inherent), so the real contract
  is dataflow: the lane-graph operands (``chunk_prev`` / ``lane_perm``
  / ``chunk_order``, per-sync exceptions in ``IDENTITY_LIVE_OK``) are
  *tainted* at the jit boundary and the taint is propagated through the
  jaxpr (including pjit/while/scan/cond bodies, to fixpoint for loop
  carries). An identity program whose gather/scatter/dynamic-slice
  *indices* carry disallowed taint violates the contract; a permuted
  program with *no* tainted indexed access means the checker went
  vacuous (the flip test).
* **no-f64 / no-host-callback** — dtype and primitive scans over every
  equation, recursively through subjaxprs.
* **words-donated** — ``donate_argnums`` covers the words buffer and
  the buffer is not aliased straight to an output (every cell), and the
  donation survives lowering (mesh cells only): jax resolves donation
  via input-output aliasing on single devices — which the words buffer
  can never satisfy, it matches no output shape — but under SPMD
  (``num_partitions > 1``) every donated operand is marked
  ``jax.buffer_donor`` and XLA frees it early. So the attribute check
  runs on the 2-device mesh lowering, where the donation is actually
  decidable.
* **collective-accounting** — compiled SPMD HLO on a 2-device mesh must
  show the same collective kinds to the instruction counter as to
  ``dist.collectives``' byte parser.
* **int32-lattice** — :func:`contracts.check_index_lattice` over every
  grid shape plus the largest ladder rung the runtime guard admits.

Run via ``python -m repro.analysis contracts`` (which forces a 2-device
CPU topology before jax initializes — do not import this module into a
process whose jax is already single-device and expect mesh cells to
work).
"""
from __future__ import annotations

import dataclasses
import re
from types import SimpleNamespace
from typing import Callable, Dict, FrozenSet, List, Optional, Sequence

import numpy as np

import jax
from jax.extend import core as jcore

from . import contracts

SYNCS = ("jacobi", "faithful", "sequential", "specmap")
BACKENDS = ("jnp", "pallas")


@dataclasses.dataclass(frozen=True)
class Access:
    """One gather/scatter/dynamic-slice whose index operand is tainted."""
    prim: str
    taint: FrozenSet[str]


@dataclasses.dataclass(frozen=True)
class Violation:
    contract: str
    cell: str
    detail: str

    def format(self) -> str:
        return f"[{self.contract}] {self.cell}: {self.detail}"


# ---------------------------------------------------------------------------
# Jaxpr walking
# ---------------------------------------------------------------------------

def _subjaxprs(params):
    """Every Jaxpr/ClosedJaxpr reachable from an eqn's params."""
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def iter_eqns(jaxpr):
    """All equations, recursively through every subjaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub)


def scan_f64(closed) -> List[str]:
    hits = []
    def vars_of(eqn):
        return list(eqn.invars) + list(eqn.outvars)
    for eqn in iter_eqns(closed.jaxpr):
        for v in vars_of(eqn):
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and dt == np.float64:
                hits.append(f"{eqn.primitive.name}: {v.aval}")
    for v in closed.jaxpr.invars + closed.jaxpr.outvars:
        dt = getattr(getattr(v, "aval", None), "dtype", None)
        if dt is not None and dt == np.float64:
            hits.append(f"boundary: {v.aval}")
    return hits


def scan_callbacks(closed) -> List[str]:
    hits = []
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if any(frag in name for frag in contracts.HOST_CALLBACK_PRIMS):
            hits.append(name)
    return hits


# ---------------------------------------------------------------------------
# Taint propagation
# ---------------------------------------------------------------------------

_EMPTY: FrozenSet[str] = frozenset()
_FIXPOINT_ROUNDS = 64


def _taint_jaxpr(jaxpr, in_taints: Sequence[FrozenSet[str]],
                 on_access: Callable[[Access], None]) -> List[FrozenSet[str]]:
    env: Dict = {}

    def read(atom) -> FrozenSet[str]:
        if isinstance(atom, jcore.Literal):
            return _EMPTY
        return env.get(atom, _EMPTY)

    def write(var, ts: FrozenSet[str]) -> None:
        # jax.extend.core exports no DropVar; an unused outvar is one
        if type(var).__name__ != "DropVar":
            env[var] = ts

    assert len(jaxpr.invars) == len(in_taints), \
        f"{len(jaxpr.invars)} invars vs {len(in_taints)} taints"
    for v, t in zip(jaxpr.invars, in_taints):
        write(v, t)
    for v in jaxpr.constvars:
        write(v, _EMPTY)

    def closed_call(closed, ts):
        return _taint_jaxpr(closed.jaxpr, ts, on_access)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        in_ts = [read(v) for v in eqn.invars]

        # indexed accesses: does lane-graph taint reach the *index* operand?
        idx_ts: FrozenSet[str] = _EMPTY
        if name in ("gather",) or name.startswith("scatter"):
            if len(eqn.invars) >= 2:
                idx_ts = in_ts[1]
        elif name == "dynamic_slice":
            idx_ts = frozenset().union(*in_ts[1:]) if in_ts[1:] else _EMPTY
        elif name == "dynamic_update_slice":
            idx_ts = frozenset().union(*in_ts[2:]) if in_ts[2:] else _EMPTY
        if idx_ts:
            on_access(Access(prim=name, taint=idx_ts))

        p = eqn.params
        if name == "jit" and isinstance(p.get("jaxpr"), jcore.ClosedJaxpr):
            out_ts = closed_call(p["jaxpr"], in_ts)
        elif name == "while" and "body_jaxpr" in p:
            cc, bc = p["cond_nconsts"], p["body_nconsts"]
            cond_consts, body_consts = in_ts[:cc], in_ts[cc:cc + bc]
            carry = list(in_ts[cc + bc:])
            for _ in range(_FIXPOINT_ROUNDS):
                body_out = closed_call(p["body_jaxpr"], body_consts + carry)
                new = [c | o for c, o in zip(carry, body_out)]
                if new == carry:
                    break
                carry = new
            closed_call(p["cond_jaxpr"], cond_consts + carry)
            out_ts = carry
        elif name == "scan" and isinstance(p.get("jaxpr"), jcore.ClosedJaxpr):
            nc, ncar = p["num_consts"], p["num_carry"]
            consts, xs = in_ts[:nc], in_ts[nc + ncar:]
            carry = list(in_ts[nc:nc + ncar])
            outs = closed_call(p["jaxpr"], consts + carry + xs)
            for _ in range(_FIXPOINT_ROUNDS):
                new = [c | o for c, o in zip(carry, outs[:ncar])]
                if new == carry:
                    break
                carry = new
                outs = closed_call(p["jaxpr"], consts + carry + xs)
            out_ts = carry + outs[ncar:]
        elif name == "cond" and p.get("branches"):
            out_ts = None
            for br in p["branches"]:
                o = closed_call(br, in_ts[1:])
                out_ts = o if out_ts is None else \
                    [a | b for a, b in zip(out_ts, o)]
        elif ("call_jaxpr" in p
              and isinstance(p["call_jaxpr"], jcore.ClosedJaxpr)
              and len(p["call_jaxpr"].jaxpr.invars) == len(eqn.invars)):
            out_ts = closed_call(p["call_jaxpr"], in_ts)
        else:
            sub = next(iter(_subjaxprs(p)), None)
            if (sub is not None and len(sub.invars) == len(eqn.invars)
                    and len(sub.outvars) == len(eqn.outvars)):
                # shard_map-style: 1:1 operand mapping
                out_ts = _taint_jaxpr(sub, in_ts, on_access)
            else:
                # conservative: union of inputs flows to every output
                # (pallas_call scratch/ref layouts land here)
                u = frozenset().union(*in_ts) if in_ts else _EMPTY
                out_ts = [u] * len(eqn.outvars)
        if len(out_ts) != len(eqn.outvars):  # defensive: stay sound
            u = frozenset().union(*in_ts) if in_ts else _EMPTY
            out_ts = [u] * len(eqn.outvars)
        for v, t in zip(eqn.outvars, out_ts):
            write(v, t)

    return [read(v) for v in jaxpr.outvars]


def lane_graph_accesses(closed, invar_names: Sequence[str]) -> List[Access]:
    """Taint the lane-graph invars and collect every indexed access whose
    index operand carries that taint."""
    in_taints = [frozenset({nm}) if nm in contracts.LANE_GRAPH_ARRAYS
                 else _EMPTY for nm in invar_names]
    accesses: List[Access] = []
    seen = set()

    def record(a: Access) -> None:
        if a not in seen:
            seen.add(a)
            accesses.append(a)

    _taint_jaxpr(closed.jaxpr, in_taints, record)
    return accesses


# ---------------------------------------------------------------------------
# Cells
# ---------------------------------------------------------------------------

def _invar_names(words, dev_rest) -> List[str]:
    """Names of the flat jit operands, aligned with the jaxpr invars
    (trace_token is static and contributes none)."""
    import jax.tree_util as jtu
    names: List[str] = []
    for path, _leaf in jtu.tree_leaves_with_path((words, dev_rest)):
        if len(path) == 1:
            names.append("words")
        else:
            key = path[-1]
            names.append(str(getattr(key, "key", key)))
    return names


def _trace(dec):
    from ..dist import sharding as S
    return dec.program.coeffs_fn.trace(
        dec.data.words, dec._dev_rest, S.trace_token())


def _cell_label(shape, sync: str, backend: str, extra: str = "") -> str:
    mode = "permuted" if shape.permuted else "identity"
    lab = f"{shape.label()}/{sync}/{backend}/{mode}"
    return f"{lab}/{extra}" if extra else lab


def check_lane_graph(closed, names, sync: str, permuted: bool,
                     cell: str) -> List[Violation]:
    accesses = lane_graph_accesses(closed, names)
    out: List[Violation] = []
    if not permuted:
        allowed = contracts.identity_live_ok(sync)
        bad = [a for a in accesses if a.taint - allowed]
        if bad:
            kinds = sorted({f"{a.prim}[{'+'.join(sorted(a.taint - allowed))}]"
                            for a in bad})
            out.append(Violation(
                "identity-lane-graph", cell,
                f"identity program indexes through lane-graph operands: "
                f"{', '.join(kinds)} (allowed for {sync}: "
                f"{sorted(allowed) or 'none'}) — the PR 3 gather-creep "
                f"regression"))
    else:
        if not any(a.taint for a in accesses):
            out.append(Violation(
                "identity-lane-graph", cell,
                "permuted program shows NO lane-graph-tainted indexed "
                "access — the gather contract cannot flip, so the checker "
                "is vacuous (taint mapping broke?)"))
    return out


def check_boundary(closed, names, cell) -> List[Violation]:
    out = []
    f64 = scan_f64(closed)
    if f64:
        out.append(Violation("no-f64", cell,
                             f"float64 values in trace: {f64[:4]}"))
    cbs = scan_callbacks(closed)
    if cbs:
        out.append(Violation("no-host-callback", cell,
                             f"host-boundary primitives in hot path: "
                             f"{sorted(set(cbs))}"))
    return out


_DONOR_ARG0 = re.compile(
    r"%arg0:\s*tensor<[^>]*>\s*\{[^}]*"
    r"(jax\.buffer_donor\s*=\s*true|tf\.aliasing_output)")


def check_donation(tr, closed, cell) -> List[Violation]:
    out = []
    donate = tuple(getattr(tr, "donate_argnums", ()) or ())
    if 0 not in donate:
        out.append(Violation(
            "words-donated", cell,
            f"words (arg 0) not in donate_argnums={donate}"))
    if closed.jaxpr.invars and closed.jaxpr.invars[0] in set(
            v for v in closed.jaxpr.outvars
            if not isinstance(v, jcore.Literal)):
        out.append(Violation(
            "words-donated", cell,
            "words buffer is aliased straight to an output — a donated "
            "buffer the caller may reuse escapes the program"))
    return out


def check_donation_lowering(stablehlo: str, cell) -> List[Violation]:
    """Donation must survive the SPMD lowering (see module docstring:
    single-device lowerings drop it by construction, mesh lowerings must
    mark words ``jax.buffer_donor``)."""
    if _DONOR_ARG0.search(stablehlo):
        return []
    return [Violation(
        "words-donated", cell,
        "no jax.buffer_donor/tf.aliasing_output on the words operand in "
        "the mesh lowering — donation dropped before the compiler, the "
        "streaming pipeline holds both buffers live")]


def check_collectives(dec, cell) -> List[Violation]:
    """Compile under a 2-device mesh; instruction counts and byte
    accounting must agree on which collective kinds occur."""
    from ..dist import collectives as C
    from ..dist import sharding as S
    from ..core.api import _decode_rules
    out: List[Violation] = []
    devs = jax.devices()
    if len(devs) < 2:
        print(f"note: single-device process, skipping collective cell "
              f"{cell} (run via `python -m repro.analysis contracts`)")
        return out
    mesh = jax.sharding.Mesh(np.array(devs[:2]), ("data",))
    with mesh, S.logical_rules(_decode_rules(mesh)):
        tr = _trace(dec)
        lowered = tr.lower()
        out += check_donation_lowering(lowered.as_text(), cell)
        hlo = lowered.compile().as_text()
    counts = C.collective_counts(hlo)
    bytes_ = C.collective_bytes(hlo)
    if set(counts) != set(bytes_):
        out.append(Violation(
            "collective-accounting", cell,
            f"kind sets disagree: counts={sorted(counts)} vs "
            f"bytes={sorted(bytes_)} — dist.collectives' HLO parse no "
            f"longer matches the instruction stream"))
    for k, n in counts.items():
        if n > 0 and bytes_.get(k, 0) <= 0:
            out.append(Violation(
                "collective-accounting", cell,
                f"{n} x {k} instructions but {bytes_.get(k, 0)} accounted "
                f"bytes — the roofline's interconnect term undercounts"))
    return out


def check_lattice(shapes) -> List[Violation]:
    out: List[Violation] = []
    for sh in shapes:
        for model in ("valid", "adversarial"):
            try:
                contracts.check_index_lattice(sh, model=model)
            except contracts.ContractViolation as e:
                out.append(Violation("int32-lattice",
                                     f"{sh.label()}/{model}", str(e)))
        k = contracts.max_damaged_segment_chunks(sh)
        if k < sh.n_chunks:
            out.append(Violation(
                "int32-lattice", sh.label(),
                f"adversarial headroom only covers damaged segments up to "
                f"{k} chunks but the shape holds {sh.n_chunks}"))
    # the largest ladder rung the runtime guard admits must itself be
    # valid-model safe (the guard and the lattice agree at the boundary)
    s_max = max(sh.s_max for sh in shapes)
    from ..core.bitstream import bucket_capacity
    rung, n = 1, 1
    while True:
        cap = bucket_capacity(n)
        if cap * 64 + contracts.write_overshoot(s_max) > contracts.INT32_MAX:
            break
        rung, n = cap, cap + 1
    duck = SimpleNamespace(
        n_units=rung, s_max=s_max,
        n_words=(contracts.INT32_MAX - 63) // 32, n_chunks=rung,
        label=lambda: f"max-admissible-rung(u{rung},s{s_max})")
    try:
        contracts.check_index_lattice(duck, model="valid")
    except contracts.ContractViolation as e:
        out.append(Violation(
            "int32-lattice", duck.label(),
            f"runtime guard admits a bucket the lattice rejects: {e}"))
    return out


# ---------------------------------------------------------------------------
# The tier-0 grid
# ---------------------------------------------------------------------------

def tier0_decoders():
    """(decoder, sync, backend) cells: 2 shapes x 4 syncs x 2 backends of
    identity plans, plus one permuted plan per backend for the flip."""
    from ..core.api import ParallelDecoder
    from ..jpeg.encoder import DatasetSpec, build_dataset
    ds_rst = build_dataset(DatasetSpec("t0-restart", n_images=2, width=48,
                                       height=32, quality=75,
                                       restart_interval=2))
    ds_one = build_dataset(DatasetSpec("t0-plain", n_images=1, width=64,
                                       height=64, quality=90))
    cells = []
    for blobs in (ds_rst.jpeg_bytes, ds_one.jpeg_bytes):
        for sync in SYNCS:
            for backend in BACKENDS:
                dec = ParallelDecoder.from_bytes(
                    list(blobs), sync=sync, backend=backend)
                cells.append((dec, sync, backend, ""))
    for backend in BACKENDS:
        dec = ParallelDecoder.from_bytes(
            list(ds_rst.jpeg_bytes), sync="jacobi", backend=backend,
            balance="roundrobin", lanes=2)
        cells.append((dec, "jacobi", backend, "flip"))
    return cells


def run(self_test: bool = False, verbose: bool = False) -> int:
    violations: List[Violation] = []
    cells = tier0_decoders()
    shapes = []
    seen_shapes = set()
    for dec, sync, backend, extra in cells:
        cell = _cell_label(dec.shape, sync, backend, extra)
        tr = _trace(dec)
        closed = tr.jaxpr
        names = _invar_names(dec.data.words, dec._dev_rest)
        if len(names) != len(closed.jaxpr.invars):
            violations.append(Violation(
                "identity-lane-graph", cell,
                f"operand-name mapping broke: {len(names)} leaves vs "
                f"{len(closed.jaxpr.invars)} invars"))
            continue
        violations += check_lane_graph(closed, names, sync,
                                       dec.shape.permuted, cell)
        violations += check_boundary(closed, names, cell)
        violations += check_donation(tr, closed, cell)
        if dec.shape not in seen_shapes:
            seen_shapes.add(dec.shape)
            shapes.append(dec.shape)
        if verbose:
            print(f"checked {cell}")

    violations += check_lattice(shapes)
    for sh in shapes:
        k = contracts.max_damaged_segment_chunks(sh)
        if verbose:
            print(f"lattice {sh.label()}: adversarial damaged-segment "
                  f"headroom {k} chunks")

    # collective accounting on one identity + one permuted jnp cell
    for dec, sync, backend, extra in cells:
        if backend != "jnp" or sync != "jacobi":
            continue
        if extra == "flip" or dec.shape.n_images == 2:
            violations += check_collectives(
                dec, _cell_label(dec.shape, sync, backend, "mesh"))

    if self_test:
        failures = run_self_test(verbose=verbose)
        for f in failures:
            violations.append(Violation("self-test", "seeded", f))

    for v in violations:
        print(v.format())
    n_cells = len(cells)
    print(f"{len(violations)} contract violation"
          f"{'s' if len(violations) != 1 else ''} across {n_cells} cells "
          f"({len(shapes)} shapes; contracts: "
          f"{', '.join(contracts.JAXPR_CONTRACTS)})")
    return 1 if violations else 0


# ---------------------------------------------------------------------------
# Seeded-violation self-test: prove the checker catches what it claims to
# ---------------------------------------------------------------------------

def seeded_gather_trace(dec):
    """An identity-plan lowering with a deliberately injected lane-graph
    gather (the PR 3 bug, reconstructed): coefficients perturbed through
    a chunk_order-indexed read of chunk_prev."""
    import functools
    import jax.numpy as jnp
    from ..dist import sharding as S
    inner = dec.program.coeffs_fn

    # the capture is the point: this closure IS the seeded bug
    @functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))  # repro: allow[unhashable-static]
    def creeping(words, dev, trace_token):
        coeffs, rounds, conv = inner(words, dev, trace_token)
        creep = dev["chunk_prev"][dev["chunk_order"]]  # the seeded gather
        coeffs = coeffs + (creep.sum() * 0).astype(coeffs.dtype)
        return coeffs, rounds, conv

    return creeping.trace(dec.data.words, dec._dev_rest, S.trace_token())


def run_self_test(verbose: bool = False) -> List[str]:
    """Returns a list of failure strings (empty = the checker works)."""
    from ..core.api import ParallelDecoder
    from ..jpeg.encoder import DatasetSpec, build_dataset
    failures: List[str] = []
    ds = build_dataset(DatasetSpec("t0-selftest", n_images=1, width=48,
                                   height=32, quality=75))
    dec = ParallelDecoder.from_bytes(list(ds.jpeg_bytes), sync="jacobi",
                                     backend="jnp")
    assert not dec.shape.permuted
    tr = seeded_gather_trace(dec)
    names = _invar_names(dec.data.words, dec._dev_rest)
    caught = check_lane_graph(tr.jaxpr, names, "jacobi", permuted=False,
                              cell="seeded-gather")
    if not caught:
        failures.append(
            "seeded lane-graph gather in an identity lowering was NOT "
            "caught — the taint analysis is broken")
    elif verbose:
        print(f"self-test: seeded gather caught ({caught[0].detail[:80]}...)")

    # f64 detector: an x64-enabled trace must trip the dtype scan
    with jax.enable_x64():
        j = jax.make_jaxpr(lambda x: x * 2.0)(np.float64(1.5))
    if not scan_f64(j):
        failures.append("f64 trace not detected by scan_f64")
    return failures
