"""Multi-host launch: ``jax.distributed`` decode with per-host JPEG feeding.

At production scale the decoder feeds accelerators on many hosts, and each
host holds only its own slice of the compressed stream. The paper's whole
point — only compressed bytes + tiny metadata cross links — extends across
the cluster: the plan is built *where the bytes live* (cf. Sodsong et
al.'s dynamic partitioning: work is split where the stream is resident),
and the only thing hosts exchange is their tiny
:class:`~repro.core.bitstream.PlanShape`.

Protocol (docs/DISTRIBUTION.md §Multi-host):

1. :func:`init_distributed` wraps ``jax.distributed.initialize`` with
   env/flag autodetection and *fail-fast validation* — inconsistent
   configuration raises immediately, an unreachable coordinator raises
   after a bounded timeout; nothing here can hang forever.
2. A :class:`HostFeed` shards the JPEG corpus across processes in
   contiguous, balanced slices; each host parses and plans only its local
   blobs (:func:`host_plan`; a host left without images participates via
   :func:`~repro.core.bitstream.empty_batch_plan`).
3. Bucket consensus: hosts publish their bucketed PlanShape through the
   ``jax.distributed`` coordination-service KV store (a few hundred bytes;
   the compressed stream never crosses hosts) and merge by elementwise max
   (:func:`~repro.core.bitstream.merge_plan_shapes`). Every process then
   pads its local :class:`~repro.core.bitstream.PlanData` to the merged
   shape and therefore traces the IDENTICAL compiled program — the PR-4
   compile-once cache keys on the shape, so N hosts x one bucket is
   exactly one trace per host, zero retraces at steady state.
4. The decode itself is host-local SPMD (chunk lanes over the local
   devices); per-host outputs are assembled into one globally-sharded
   coefficient batch over a host-spanning mesh
   (``jax.make_array_from_process_local_data`` — pure layout, no
   collective). On CPU test clusters XLA cannot run cross-process
   computations at all, which is precisely why the consensus rides the
   coordination service instead of an allgather.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..core.api import DecodeOutput, ParallelDecoder, _sequential_chunk_bits
from ..core.bitstream import (BatchPlan, BatchValidation, ImageGeometry,
                              PlanShape, bucket_capacity, consensus_plan,
                              merge_plan_shapes, plan_shape, validate_batch)
from ..jpeg.format import parse_jpeg, unstuff_scan

_WIRE_VERSION = 1


# ---------------------------------------------------------------------------
# Distributed context
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistContext:
    """One process's view of the launch topology.

    ``initialized`` records whether ``jax.distributed`` is actually up
    (single-process contexts never touch it, so the whole module works
    unmodified on one host with zero configuration).
    """

    process_id: int
    num_processes: int
    coordinator: Optional[str]
    initialized: bool

    @property
    def is_main(self) -> bool:
        return self.process_id == 0


SINGLE_PROCESS = DistContext(process_id=0, num_processes=1,
                             coordinator=None, initialized=False)


def _env_first(*names: str) -> Optional[str]:
    for n in names:
        v = os.environ.get(n)
        if v not in (None, ""):
            return v
    return None


def process_info() -> DistContext:
    """The ambient context: what jax already knows about the cluster.

    Safe to call whether or not :func:`init_distributed` ran — a plain
    single-process jax reports (0, 1). A backend that fails to initialise
    raises here instead of passing for a single process.
    """
    import jax
    pid, n = jax.process_index(), jax.process_count()
    return DistContext(process_id=int(pid), num_processes=int(n),
                       coordinator=_env_first("REPRO_COORDINATOR",
                                              "JAX_COORDINATOR_ADDRESS"),
                       initialized=_coordination_client() is not None)


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     *, timeout_s: int = 120) -> DistContext:
    """Initialize ``jax.distributed`` with autodetection and validation.

    Resolution order per field: explicit argument, then
    ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID``,
    then the ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID`` equivalents. With nothing configured (or
    ``num_processes == 1``) this is a single-process no-op returning
    :data:`SINGLE_PROCESS`-style context — the same code path runs on a
    laptop and on a cluster.

    Fail-fast guarantees (a distributed launch must never hang silently):

    * inconsistent flags — a multi-process count without a coordinator
      address or process id, a count <= 0, an id out of range — raise
      ``ValueError`` immediately, before any network activity;
    * an unreachable coordinator or a miscounted cluster raises
      ``RuntimeError`` after ``timeout_s`` seconds (threaded into
      ``jax.distributed.initialize(initialization_timeout=)``) with the
      topology in the message.
    """

    def _int(v, name):
        if v is None:
            return None
        try:
            return int(v)
        except (TypeError, ValueError):
            raise ValueError(f"{name} must be an integer, got {v!r}")

    if coordinator is None:
        coordinator = _env_first("REPRO_COORDINATOR",
                                 "JAX_COORDINATOR_ADDRESS")
    if num_processes is None:
        num_processes = _int(_env_first("REPRO_NUM_PROCESSES",
                                        "JAX_NUM_PROCESSES"),
                             "num_processes")
    if process_id is None:
        process_id = _int(_env_first("REPRO_PROCESS_ID", "JAX_PROCESS_ID"),
                          "process_id")

    if num_processes is None and coordinator is None and process_id is None:
        # these are local *config* values resolved from this host's env,
        # not runtime process identity  # repro: allow[host-divergence]
        return SINGLE_PROCESS
    if num_processes is None:
        raise ValueError(
            "init_distributed: a coordinator/process id was configured but "
            "num_processes was not — pass num_processes= or set "
            "REPRO_NUM_PROCESSES on every host")
    num_processes = int(num_processes)
    if num_processes <= 0:
        raise ValueError(
            f"init_distributed: num_processes must be positive, got "
            f"{num_processes}")
    if num_processes == 1:
        return DistContext(0, 1, coordinator, False)
    if coordinator is None:
        raise ValueError(
            f"init_distributed: {num_processes} processes but no "
            f"coordinator address — pass coordinator='host:port' or set "
            f"REPRO_COORDINATOR (refusing to guess: a wrong address would "
            f"hang every host)")
    if process_id is None:
        raise ValueError(
            f"init_distributed: {num_processes} processes but no "
            f"process_id — pass process_id= or set REPRO_PROCESS_ID "
            f"(0..{num_processes - 1}, unique per host)")
    process_id = int(process_id)
    if not 0 <= process_id < num_processes:
        raise ValueError(
            f"init_distributed: process_id {process_id} out of range for "
            f"{num_processes} processes (need 0..{num_processes - 1})")

    if _coordination_client() is not None:
        # already initialized (earlier call, or the launcher did it):
        # verify the ambient topology matches rather than re-initializing
        import jax
        have = (int(jax.process_index()), int(jax.process_count()))
        want = (process_id, num_processes)
        if have != want:
            raise RuntimeError(
                f"jax.distributed is already initialized as process "
                f"{have[0]}/{have[1]}, which contradicts the requested "
                f"{want[0]}/{want[1]}")
        return DistContext(process_id, num_processes, coordinator, True)

    if process_id != 0:
        # Pre-validate reachability with a plain TCP probe (retrying up to
        # timeout_s: the coordinator may legitimately come up after the
        # workers). The XLA distributed client does NOT raise on a connect
        # deadline — it hard-kills the process with an abseil FATAL — so a
        # wrong address must be caught here, at the Python level, where the
        # launcher can report it.
        _wait_for_coordinator(coordinator, timeout_s,
                              who=f"process {process_id}/{num_processes}")

    import jax
    try:
        jax.distributed.initialize(coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id,
                                   initialization_timeout=timeout_s)
    except Exception as e:
        raise RuntimeError(
            f"jax.distributed.initialize failed for process "
            f"{process_id}/{num_processes} (coordinator {coordinator}, "
            f"timeout {timeout_s}s): {e}. Check that the coordinator is "
            f"reachable and that EVERY host was launched with the same "
            f"num_processes and a unique process_id.") from e
    return DistContext(process_id, num_processes, coordinator, True)


def _wait_for_coordinator(coordinator: str, timeout_s: int,
                          who: str) -> None:
    """Block until a TCP connect to ``coordinator`` succeeds, or raise."""
    import socket
    import time
    try:
        host, port_s = coordinator.rsplit(":", 1)
        port = int(port_s)
    except ValueError:
        raise ValueError(
            f"coordinator address must be 'host:port', got {coordinator!r}")
    deadline = time.monotonic() + timeout_s
    last_err: Optional[Exception] = None
    while time.monotonic() < deadline:
        try:
            socket.create_connection((host, port), timeout=1.0).close()
            return
        except OSError as e:
            last_err = e
            time.sleep(0.25)
    raise RuntimeError(
        f"{who}: coordinator {coordinator} unreachable after {timeout_s}s "
        f"({last_err}) — check the address/port and that process 0 is up")


# ---------------------------------------------------------------------------
# Tiny-metadata exchange over the coordination service
# ---------------------------------------------------------------------------

def _coordination_client():
    """The jax.distributed coordination-service client, or None.

    Internal-API probe in the style of ``dist.sharding._active_mesh`` —
    guarded so a jax relayout degrades to a clear runtime error, never an
    import error.
    """
    try:
        from jax._src import distributed
        return distributed.global_state.client
    except (ImportError, AttributeError):  # pragma: no cover - moved
        return None


_exchange_counter = itertools.count()
# KV keys are write-once on the coordination service, so a *reused* tag
# (e.g. decode_multihost(..., tag="step") every training step) must not
# collide with — or silently read — an earlier round's keys. Each tag
# carries a per-process use counter into the key; processes stay in sync
# as long as they perform the same exchanges in the same order, which is
# the same ordering contract the auto-generated tags rely on.
_tag_rounds: Dict[str, int] = {}


def exchange(payload: str, ctx: DistContext, tag: Optional[str] = None,
             *, timeout_ms: int = 120_000) -> List[str]:
    """All-to-all of tiny strings via the coordination-service KV store.

    Every process publishes ``payload`` under a shared ``tag`` and reads
    every peer's value; returns the list ordered by process id. This is
    the multi-host metadata channel (PlanShapes, unit counts, stats) — a
    few hundred bytes per host, no XLA computation, so it works on any
    backend including multi-process CPU test clusters.

    ``tag`` defaults to a module-level counter; an explicit tag may be
    reused freely (each use gets a fresh key round). Either way the
    correctness condition is that every process performs the same
    exchanges in the same order. A bounded ``timeout_ms`` turns a missing
    peer — the classic mismatched-process-count deadlock — into a clear
    error. Keys are never deleted (peers may read late); they are a few
    hundred bytes per exchange and live only for the process group.
    """
    if ctx.num_processes == 1:
        return [payload]
    client = _coordination_client()
    if client is None:
        raise RuntimeError(
            "exchange() needs jax.distributed to be initialized "
            "(init_distributed) when num_processes > 1")
    if tag is None:
        tag = f"auto{next(_exchange_counter)}"
    rnd = _tag_rounds.get(tag, 0)
    _tag_rounds[tag] = rnd + 1
    base = f"repro/mh/{tag}#{rnd}"
    client.key_value_set(f"{base}/{ctx.process_id}", payload)
    out = []
    for peer in range(ctx.num_processes):
        try:
            out.append(client.blocking_key_value_get(f"{base}/{peer}",
                                                     timeout_ms))
        except Exception as e:
            raise RuntimeError(
                f"exchange({tag!r}): process {ctx.process_id} timed out "
                f"after {timeout_ms}ms waiting for process {peer} of "
                f"{ctx.num_processes} — a peer likely died, hung, or was "
                f"launched with a different num_processes") from e
    return out


def barrier(ctx: DistContext, tag: str, *, timeout_ms: int = 120_000) -> None:
    """Cross-process barrier (coordination service); no-op single-process."""
    if ctx.num_processes == 1:
        return
    client = _coordination_client()
    if client is None:
        raise RuntimeError("barrier() needs jax.distributed initialized")
    client.wait_at_barrier(f"repro/mh/barrier/{tag}", timeout_ms)


# ---------------------------------------------------------------------------
# PlanShape wire codec (KV store carries strings)
# ---------------------------------------------------------------------------

def shape_to_wire(shape: PlanShape) -> str:
    d = dataclasses.asdict(shape)
    d["_v"] = _WIRE_VERSION
    return json.dumps(d, sort_keys=True)


def shape_from_wire(wire: str) -> PlanShape:
    d = json.loads(wire)
    v = d.pop("_v", None)
    if v != _WIRE_VERSION:
        raise ValueError(
            f"PlanShape wire version mismatch: got {v}, expected "
            f"{_WIRE_VERSION} — all hosts must run the same repro build")
    g = d.pop("geometry")
    if g is not None:
        g = ImageGeometry(**{k: tuple(v) if isinstance(v, list) else v
                             for k, v in g.items()})
    return PlanShape(geometry=g, **d)


# ---------------------------------------------------------------------------
# Per-host JPEG feeding
# ---------------------------------------------------------------------------

class HostFeed:
    """Shards a JPEG corpus across processes; a host keeps only its slice.

    The split is contiguous and balanced (the first ``len % n`` hosts get
    one extra image), so concatenating per-host outputs in process order
    reproduces the single-process decode of the whole corpus — the
    bit-identity contract of :func:`decode_multihost`. Hosts past the end
    of a short corpus simply hold zero blobs and participate with inert
    plans.
    """

    def __init__(self, local_blobs: Sequence[bytes], ctx: DistContext):
        self.local_blobs: List[bytes] = list(local_blobs)
        self.ctx = ctx

    @staticmethod
    def bounds(n_items: int, num_processes: int) -> List[int]:
        """Slice boundaries: host h owns [bounds[h], bounds[h+1])."""
        if num_processes <= 0:
            raise ValueError(f"num_processes must be positive, "
                             f"got {num_processes}")
        q, r = divmod(n_items, num_processes)
        sizes = [q + (1 if h < r else 0) for h in range(num_processes)]
        out = [0]
        for s in sizes:
            out.append(out[-1] + s)
        return out

    @classmethod
    def from_corpus(cls, blobs: Sequence[bytes],
                    ctx: DistContext) -> "HostFeed":
        """This host's contiguous slice of a globally-known corpus list."""
        b = cls.bounds(len(blobs), ctx.num_processes)
        lo, hi = b[ctx.process_id], b[ctx.process_id + 1]
        return cls(list(blobs[lo:hi]), ctx)

    def __len__(self) -> int:
        return len(self.local_blobs)

    def batches(self, batch_size: int) -> List[List[bytes]]:
        """The local slice in decode-batch-sized groups."""
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive, got {batch_size}")
        return [self.local_blobs[i: i + batch_size]
                for i in range(0, len(self.local_blobs), batch_size)]


# ---------------------------------------------------------------------------
# Host-local planning + bucket consensus
# ---------------------------------------------------------------------------

def host_plan(local_blobs: Sequence[bytes], *, chunk_bits: int = 1024,
              seq_chunks: int = 32, balance: str = "none",
              lanes: Optional[int] = None,
              validation: Optional[BatchValidation] = None) -> BatchPlan:
    """Plan this host's local blobs (inert-only plan when it has none).

    Thin re-export of :func:`repro.dist.plan.local_batch_plan` — the
    planner lives with the other plan machinery; this module owns the
    exchange/consensus protocol around it. ``validation`` switches to
    resilient planning (damaged local blobs quarantined, never raised).
    """
    from ..dist.plan import local_batch_plan
    return local_batch_plan(local_blobs, chunk_bits=chunk_bits,
                            seq_chunks=seq_chunks, balance=balance,
                            lanes=lanes, validation=validation)


def plan_consensus(plan: BatchPlan, ctx: DistContext,
                   tag: Optional[str] = None, *, bucket: bool = True,
                   timeout_ms: int = 120_000):
    """One consensus round: publish my shape, merge everyone's, align.

    Returns ``(aligned_plan, merged_shape)``. Single-process this
    degenerates to ``(plan, plan_shape(plan))`` — the exact PR-4 path.
    """
    shape = plan_shape(plan, bucket=bucket)
    wires = exchange(shape_to_wire(shape), ctx, tag, timeout_ms=timeout_ms)
    merged = merge_plan_shapes([shape_from_wire(w) for w in wires])
    return consensus_plan(plan, merged), merged


# ---------------------------------------------------------------------------
# The multi-host decode
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MultiHostDecodeOutput:
    """Per-host decode result plus the global view.

    ``local`` is this host's :class:`DecodeOutput` (coeffs sliced to the
    host's real unit count). ``unit_counts`` is every host's real unit
    count (exchanged as tiny ints), so ``global_coeffs`` — one
    host-sharded ``jax.Array`` of shape ``(num_processes * shape.n_units,
    64)``, row block h = host h's capacity-padded coefficients — can be
    sliced back to real rows by any consumer. ``compiles`` counts this
    host's program traces for the decode's bucket (the compile-once
    assertion surface).
    """

    local: DecodeOutput
    shape: PlanShape
    process_id: int
    num_processes: int
    unit_counts: List[int]
    global_coeffs: Optional[object] = None
    compiles: int = 0
    # resilient decodes (validate=True): this host's per-image STATUS_*
    # array, and every host's status list in process order (tiny ints over
    # the coordination service — damage is reportable cluster-wide without
    # moving pixels)
    status: Optional[np.ndarray] = None
    host_statuses: Optional[List[List[int]]] = None


def assemble_global_coeffs(coeffs, shape: PlanShape, ctx: DistContext):
    """One globally-sharded coefficient batch over the host-spanning mesh.

    Pure data layout (``jax.make_array_from_process_local_data``) — each
    host contributes its capacity-padded row block, replicated over its
    local devices; no collective runs, so this works even on multi-process
    CPU where XLA cannot span hosts.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from .mesh import make_hosts_mesh
    cap = shape.n_units
    local = np.zeros((cap, 64), dtype=np.int32)
    real = np.asarray(coeffs)
    local[: real.shape[0]] = real
    mesh = make_hosts_mesh()
    sharding = NamedSharding(mesh, P("hosts"))
    return jax.make_array_from_process_local_data(sharding, local)


def decode_multihost(local_blobs: Sequence[bytes],
                     ctx: Optional[DistContext] = None, *,
                     chunk_bits: int = 1024, seq_chunks: int = 32,
                     sync: str = "jacobi", backend: Optional[str] = None,
                     use_kernels: bool = False,
                     interpret: Optional[bool] = None,
                     balance: str = "none", lanes: Optional[int] = None,
                     emit: str = "coeffs", mesh: str = "local",
                     assemble: bool = True, tag: Optional[str] = None,
                     validate: bool = False,
                     timeout_ms: int = 120_000) -> MultiHostDecodeOutput:
    """Decode one global batch whose bytes are spread across hosts.

    Every process calls this with its *local* blobs (see
    :class:`HostFeed`); the result is bit-identical to a single-process
    ``decode_batch`` of the hosts' corpora concatenated in process order.
    ``sync="sequential"`` adds one pre-round settling the data-dependent
    chunk size (elementwise max of the hosts' ladder-rounded candidates) so
    the framing constant agrees before shapes are exchanged.

    ``mesh="local"`` shards the host's chunk lanes over its local devices
    when it has more than one (``decode_on``); ``mesh="none"`` stays
    single-device. The decode never requires a cross-host XLA computation;
    ``assemble`` controls whether the per-host outputs are additionally
    laid out as one host-sharded global array (coeffs only).

    ``validate=True`` (must agree across hosts — it changes the exchange
    schedule) classifies each local blob before planning: a damaged blob
    is quarantined or partially recovered host-locally and NEVER raises.
    This is load-bearing in a collective decode — one host dying on a
    corrupt feed would strand every peer at the consensus exchange until
    timeout. Per-image statuses ride the result (``status``,
    ``host_statuses``).
    """
    if ctx is None:
        ctx = process_info()
    if mesh not in ("local", "none"):
        raise ValueError(f"mesh must be 'local' or 'none', got {mesh!r}")
    if tag is None:
        tag = f"decode{next(_exchange_counter)}"
    from ..kernels.backend import resolve_backend
    backend = resolve_backend(backend, use_kernels)

    validation: Optional[BatchValidation] = None
    if validate:
        validation = validate_batch(local_blobs)

    if sync == "sequential":
        # settle the data-dependent framing constant first: every host
        # proposes the ladder-rounded chunk size its local segments need,
        # the consensus is the max — identical to what a single process
        # holding the whole corpus would compute
        if validation is not None:
            # size from the surviving scans only; a raw parse here would
            # re-raise on exactly the damaged blobs validation absorbed
            live = [(r.clean, r.rst_bits) for r in validation.reports
                    if r.clean is not None]
            mine = (_sequential_chunk_bits(live, bucket=True) if live
                    else -(-bucket_capacity(32) // 32) * 32)
        elif local_blobs:
            unstuffed = [unstuff_scan(parse_jpeg(b).scan_data)
                         for b in local_blobs]
            mine = _sequential_chunk_bits(unstuffed, bucket=True)
        else:
            mine = -(-bucket_capacity(32) // 32) * 32
        votes = exchange(str(mine), ctx, f"{tag}/chunkbits",
                         timeout_ms=timeout_ms)
        chunk_bits = max(int(v) for v in votes)

    plan = host_plan(local_blobs, chunk_bits=chunk_bits,
                     seq_chunks=seq_chunks, balance=balance, lanes=lanes,
                     validation=validation)
    plan, merged = plan_consensus(plan, ctx, f"{tag}/shape",
                                  timeout_ms=timeout_ms)

    dec = ParallelDecoder(plan, sync=sync, backend=backend,
                          interpret=interpret, shape=merged)

    local_mesh = None
    if mesh == "local":
        import jax
        if len(jax.local_devices()) > 1:
            from .mesh import make_local_data_mesh
            local_mesh = make_local_data_mesh()
    out = (dec.decode_on(local_mesh, emit=emit) if local_mesh is not None
           else dec.decode(emit=emit))

    counts = exchange(str(plan.total_units), ctx, f"{tag}/units",
                      timeout_ms=timeout_ms)
    unit_counts = [int(c) for c in counts]

    status = None
    host_statuses = None
    if validation is not None:
        status = validation.status
        wires = exchange(json.dumps([int(s) for s in status]), ctx,
                         f"{tag}/status", timeout_ms=timeout_ms)
        host_statuses = [json.loads(w) for w in wires]

    global_coeffs = None
    if assemble and ctx.initialized:
        global_coeffs = assemble_global_coeffs(out.coeffs, merged, ctx)

    return MultiHostDecodeOutput(
        local=out, shape=merged, process_id=ctx.process_id,
        num_processes=ctx.num_processes, unit_counts=unit_counts,
        global_coeffs=global_coeffs, compiles=dec.program.compiles,
        status=status, host_statuses=host_statuses)


# ---------------------------------------------------------------------------
# Per-host decode-stats aggregation
# ---------------------------------------------------------------------------

def gather_decode_stats(stats: Dict, ctx: Optional[DistContext] = None,
                        tag: Optional[str] = None, *,
                        timeout_ms: int = 120_000) -> List[Dict]:
    """Every host's ``decode_stats()`` dict, ordered by process id.

    Compile counters are per-process by construction (each host traces its
    own programs); aggregating by summation would misreport the
    compile-once invariant, so this returns the per-host dicts and leaves
    the "exactly one trace per bucket per host" assertion to the caller.
    """
    if ctx is None:
        ctx = process_info()
    wires = exchange(json.dumps(stats), ctx, tag or f"stats{next(_exchange_counter)}",
                     timeout_ms=timeout_ms)
    return [json.loads(w) for w in wires]
