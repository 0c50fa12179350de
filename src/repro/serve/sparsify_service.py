"""Batched sparsification serving: size-bucketed `GraphBatch` dispatch.

The production north star is many graphs per device dispatch, not one.
`lgrass_sparsify_batch` already amortises compile + dispatch across a
padded batch — since the recovery refactor the whole pipeline (phase 1
AND the Algorithm-6 replay) is one fused device program, so a bucket is
served by exactly one dispatch with no host round-trip between phases.
This module adds the traffic-facing policy:

  * **bucketing** — a request stream contains arbitrary (n, L) sizes,
    and every distinct padded shape is a fresh XLA compile. We round the
    pad targets up to powers of two (with a small floor), so the number
    of compiled programs is logarithmic in the size range instead of
    linear in the number of distinct sizes seen. The recovery accept
    buffer (`b_cap`) is bucketed the same way, keyed off the bucket's
    default budget, so default-budget traffic reuses one program per
    shape bucket.
  * **chunking** — buckets are dispatched in batches of at most
    `max_batch_size` graphs to bound device memory.
  * **batch-dim bucketing** — the leading batch axis is itself a
    compiled dimension, so each chunk is padded up to a power of two
    with trivial placeholder graphs (dropped from the results); chunk
    sizes 5, 7, 12 share the B=8/8/16 programs instead of compiling
    three times.
  * **schedule policy** — the phase-1 marking engine is a per-service
    config (`schedule="chunked"` by default) and its block size is
    resolved *per bucket* from the padded edge count
    (`core.pow2.auto_chunk`), so every graph in a bucket shares one
    compiled block size and `warmup` compiles exactly the programs
    traffic will request.
  * **BFS-engine policy** — the traversal engine (`bfs_engine=
    "doubling"` by default: hop-doubling graph BFS + Euler-tour tree
    rooting, O(log n) rounds on diameter-bound inputs) is a compiled-
    program key like the block size, resolved per bucket through one
    hook (`_bfs_engine`) that both the request path and `warmup` use.
  * **warmup** — `warmup(sizes)` pre-compiles the bucket programs for
    anticipated request shapes off the request path; compile counts and
    wall-clock are surfaced in `ServiceStats`.

The serving plane on top of the bucketing (PR 6):

  * **async dispatch** (`async_dispatch=True`) — JAX dispatch is
    already asynchronous; the sync path wastes that by calling
    `jax.device_get` after every chunk. The async path enqueues EVERY
    chunk's device program first, holding the per-chunk `jax.Array`
    dicts, and only then drains them in request order — host result
    assembly for chunk k overlaps device compute of chunks k+1..K.
  * **buffer donation** (`donate=True`) — chunks dispatch through
    `lgrass_device_batched_donated` (`donate_argnums` on the padded
    u/v/w/edge_valid/budget arrays, exactly as `serve/serve_step.py`
    donates decode caches), so XLA reuses the request's input buffers
    for its outputs instead of allocating fresh device memory per call.
    Host-side, a per-bucket pinned staging pool reuses the padded numpy
    arrays across requests (the device transfer is a forced copy, so
    refilling the pool can never race a donated in-flight buffer).
  * **batch-axis sharding** (`mesh=...`) — `lgrass_device_batched` is
    embarrassingly parallel over its leading (graph) axis, so a chunk's
    batch axis is sharded across the mesh
    (`core.distributed.shard_batch_leading`); one pod serves one
    mega-bucket. The batch
    pad target rounds up to a multiple of the mesh size so every shard
    gets equal rows. The graphs never meet, but each batch-dependent
    while loop's predicate is one `pred[]` all-reduce a round, so every
    chip runs as many rounds as the slowest lane of the whole batch.
  * **on-path compile accounting** — every dispatch signature
    (n_bucket, L_bucket, B_pad, b_cap) is checked against the set
    `warmup` compiled; signatures first seen on the request path count
    in `ServiceStats.n_on_path_compiles`. The policy: a request whose
    explicit budget exceeds `default_budget(n_bucket)` widens `b_cap`
    to the next pow2 bucket — a program `warmup(sizes)` alone never
    compiled. Pass those budgets to `warmup(..., budgets=[...])` to
    pre-compile the wide-budget programs; after that, steady traffic
    can assert `stats.n_on_path_compiles == 0`.

Results come back in request order and are bit-identical to per-graph
`lgrass_sparsify` under every mode — sync, async, donated, sharded
(tests/test_batch.py, tests/test_service_plane.py).

Measurement: the host phases of a request are
`jax.profiler.TraceAnnotation` spans (`svc.bucket`, `svc.stage`, inside
it `svc.shard` when sharding, `svc.dispatch`, `svc.drain`), and
`ServiceStats` sums the programs' per-graph loop round counts
(`loop_rounds`) beside what the vmapped loops ran in lockstep
(`loop_lane_rounds`) and what they would run if each shard's loops
stopped with its own lanes (`loop_chip_rounds`).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.baseline import default_budget
from repro.core.distributed import (batch_sharding, mesh_size,
                                    shard_batch_leading)
from repro.core.graph import (PAD_ENDPOINT, PAD_WEIGHT, Graph, GraphBatch,
                              trivial_graph)
from repro.core.pow2 import auto_chunk, next_pow2
from repro.core.sparsify import (
    LOOPS,
    SparsifyResult,
    _bucket_b_cap,
    _result_from_device,
    lgrass_device_batched,
    lgrass_device_batched_donated,
)


def _placeholder_graph() -> Graph:
    """Smallest valid graph; pads the batch axis (results discarded).

    Must fit EVERY bucket — the (n=1, m=0) trivial graph does; the old
    (n=2, m=1) filler crashed buckets smaller than (2, 1)."""
    return trivial_graph()


@dataclasses.dataclass
class ServiceStats:
    n_graphs: int = 0
    n_dispatches: int = 0
    n_padded_edge_slots: int = 0   # total L_bucket * B_pad over dispatches
    n_real_edge_slots: int = 0     # real edges of real (requested) graphs
    # the two distinct kinds of padding a dispatch carries:
    n_batch_pad_edge_slots: int = 0  # placeholder rows: L_bucket * n_fill
    n_shape_pad_edge_slots: int = 0  # real rows' tail: L_bucket*B_real - m
    bucket_counts: Dict[Tuple[int, int], int] = dataclasses.field(
        default_factory=dict
    )
    n_warmup_dispatches: int = 0   # compiles triggered off the request path
    warmup_seconds: float = 0.0
    # dispatch signatures (n_bucket, L_bucket, B_pad, b_cap) first seen on
    # the request path — i.e. programs warmup never compiled. Counted once
    # per signature (XLA caches the compile); see the module docstring for
    # the b_cap-widening policy that makes this nonzero.
    n_on_path_compiles: int = 0
    # per while loop (`core.sparsify.LOOPS`), over the request path's
    # dispatches: the rounds each real graph needed, summed, and the
    # rounds the batch ran for them — B_real x the dispatch's most
    # rounds, since a vmapped loop runs until its slowest lane is done.
    # 1 - loop_rounds / loop_lane_rounds is the share of a loop's lane
    # rounds spent waiting in lockstep. loop_chip_rounds: over the shards
    # of a sharded dispatch, each shard's real rows x its own slowest
    # row, what the shards would run if each stopped with its own lanes;
    # 1 - loop_chip_rounds / loop_lane_rounds is the share spent waiting
    # on another shard. Without a mesh it equals loop_lane_rounds.
    loop_rounds: Dict[str, int] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(LOOPS, 0))
    loop_lane_rounds: Dict[str, int] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(LOOPS, 0))
    loop_chip_rounds: Dict[str, int] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(LOOPS, 0))

    def count_loops(self, rows, n_real: int, shards: int = 1):
        """Add one dispatch's (B_pad, len(LOOPS)) round counts, whose
        first `n_real` rows are real graphs, split into `shards` equal
        row blocks as `core.distributed.batch_sharding` splits them."""
        rows = np.asarray(rows, np.int64).reshape(-1, len(LOOPS))
        per = rows.shape[0] // shards
        real = np.clip(n_real - per * np.arange(shards), 0, per)
        slowest = rows.reshape(shards, per, len(LOOPS)).max(axis=1)
        for j, name in enumerate(LOOPS):
            self.loop_rounds[name] += int(rows[:n_real, j].sum())
            self.loop_lane_rounds[name] += n_real * int(rows[:, j].max())
            self.loop_chip_rounds[name] += int(real @ slowest[:, j])

    @property
    def padding_overhead(self) -> float:
        """Fraction of dispatched edge slots that were padding (both
        kinds: batch-axis placeholder rows AND real rows' shape tail)."""
        if self.n_padded_edge_slots == 0:
            return 0.0
        return (self.n_batch_pad_edge_slots + self.n_shape_pad_edge_slots
                ) / self.n_padded_edge_slots

    @property
    def batch_pad_overhead(self) -> float:
        """Fraction of dispatched edge slots burned on placeholder rows
        (the pow2 batch-axis fill). Tune with max_batch_size / warmup
        batch_sizes."""
        if self.n_padded_edge_slots == 0:
            return 0.0
        return self.n_batch_pad_edge_slots / self.n_padded_edge_slots

    @property
    def shape_pad_overhead(self) -> float:
        """Fraction of dispatched edge slots burned padding real graphs
        up to their (n_bucket, L_bucket) shape. Tune with the bucket
        floors."""
        if self.n_padded_edge_slots == 0:
            return 0.0
        return self.n_shape_pad_edge_slots / self.n_padded_edge_slots


class _StagingPool:
    """Per-(B_pad, L_bucket) pinned host buffers for padded chunks.

    Steady-state traffic refills pooled numpy arrays instead of
    allocating a fresh `GraphBatch` per chunk. Reuse is guarded by a
    FENCE: host->device transfers on this backend are themselves
    asynchronous (the dispatch reads the host buffer when the program
    actually runs — observed on CPU PJRT, where refilling a live
    staging buffer corrupted in-flight async chunks), and blocking on
    the transfer is no better (it queues behind pending compute, which
    would serialize the whole async plane). So each buffer set carries
    the `jax.Array` output of the dispatch that last used it: outputs
    ready => the program ran => its input transfers are consumed => the
    buffers are reusable. `acquire` picks a fenced-out set without
    blocking, growing the pool to the max number of in-flight chunks
    per shape (steady state allocates nothing).
    """

    def __init__(self):
        # key -> list of [bufs_tuple, fence]; fence None = free now
        self._sets: Dict[Tuple[int, int], List[list]] = {}

    def acquire(self, B_pad: int, L_bucket: int) -> list:
        """A [bufs, fence] entry whose buffers are provably not read by
        any in-flight dispatch; never blocks (allocates when all sets
        are fenced). Caller must re-arm entry[1] after dispatching."""
        sets = self._sets.setdefault((B_pad, L_bucket), [])
        for entry in sets:
            fence = entry[1]
            if fence is None or bool(fence.is_ready()):
                entry[1] = None
                return entry
        entry = [
            (
                np.empty((B_pad, L_bucket), np.int32),
                np.empty((B_pad, L_bucket), np.int32),
                np.empty((B_pad, L_bucket), np.float32),
                np.empty((B_pad, L_bucket), bool),
                np.empty((B_pad,), np.int32),
            ),
            None,
        ]
        sets.append(entry)
        return entry

    @property
    def n_buffer_sets(self) -> int:
        return sum(len(v) for v in self._sets.values())

    @staticmethod
    def fill(bufs, graphs: Sequence[Graph]):
        """Pad-fill (u, v, w, edge_valid, budget) staging arrays with the
        leading len(graphs) rows holding the real graphs and the tail
        rows left as all-padding placeholder rows."""
        u, v, w, ev, bb = bufs
        u.fill(PAD_ENDPOINT)
        v.fill(PAD_ENDPOINT)
        w.fill(PAD_WEIGHT)
        ev.fill(False)
        bb.fill(1)  # placeholder rows get the trivial budget
        for i, g in enumerate(graphs):
            m = g.m
            u[i, :m] = g.u
            v[i, :m] = g.v
            w[i, :m] = g.w
            ev[i, :m] = True
        return bufs


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One compiled-program signature of the service, in auditable form:
    the jit callable, abstract argument shapes (with the mesh's batch
    sharding when the service shards), and the static kwargs — exactly
    what `_dispatch` would run for that signature, so compiling a spec
    compiles the dispatched program. Consumed by the static auditor
    (`repro.analysis.jaxpr_audit.audit_service`), which traces fn over
    args and walks the jaxpr."""
    name: str
    signature: Tuple[int, int, int, int]   # (n_bucket, L_bucket, B_pad, b_cap)
    fn: object                             # the jit-wrapped callable
    args: tuple                            # jax.ShapeDtypeStruct per array arg
    static_kwargs: dict


@dataclasses.dataclass
class _PendingChunk:
    """One dispatched chunk awaiting drain: the device output dict plus
    everything needed to scatter rows back into request order."""
    idxs: List[int]          # request indices of the real rows
    Ls: List[int]            # per-row true edge counts (result slicing)
    device: dict             # jax.Array outputs of the fused program


class SparsifyService:
    """Sparsify request batches with a bounded set of compiled shapes.

    >>> svc = SparsifyService(async_dispatch=True, donate=True)
    >>> svc.warmup([(100, 300)])             # optional: compile off-path
    >>> results = svc.sparsify(list_of_graphs)   # request order preserved

    async_dispatch: enqueue every chunk's device program before draining
    any result (overlaps host assembly with device compute). donate:
    dispatch through the donated program + pinned staging pool. mesh:
    shard the batch axis of each chunk across the mesh (requires
    recovery="device", as do the other serving-plane modes).
    """

    def __init__(
        self,
        k_cap: int = 32,
        parallel: bool = True,
        max_batch_size: int = 64,
        min_n_bucket: int = 16,
        min_L_bucket: int = 32,
        recovery: str = "device",
        schedule: str = "chunked",
        p1_chunk: Optional[int] = None,
        bfs_engine: str = "doubling",
        async_dispatch: bool = False,
        donate: bool = False,
        mesh=None,
    ):
        self.k_cap = k_cap
        self.parallel = parallel
        self.max_batch_size = max_batch_size
        self.min_n_bucket = min_n_bucket
        self.min_L_bucket = min_L_bucket
        self.recovery = recovery
        self.schedule = schedule
        self.p1_chunk = p1_chunk
        self.bfs_engine = bfs_engine
        self.async_dispatch = async_dispatch
        self.donate = donate
        self.mesh = mesh
        if recovery == "device":
            pass
        elif recovery == "host":
            if async_dispatch or donate or mesh is not None:
                raise ValueError(
                    "async_dispatch/donate/mesh require recovery='device' "
                    "(the host oracle tail blocks per chunk by design)"
                )
        else:
            raise ValueError(f"unknown recovery mode {recovery!r}")
        self.stats = ServiceStats()
        self._pool = _StagingPool()
        self._warmed: Set[Tuple[int, int, int, int]] = set()
        self._seen: Set[Tuple[int, int, int, int]] = set()

    # ---------------------------------------------------------- policies

    def _p1_chunk(self, L_bucket: int) -> Optional[int]:
        """Per-bucket phase-1 block size policy.

        The scheduler's auto policy (`core.pow2.auto_chunk`) is a
        function of the *padded* edge count, so it is resolved here from
        the bucket — every graph in a bucket shares one compiled block
        size, and `warmup` compiles exactly the program traffic will
        request. An explicit `p1_chunk` pins all buckets instead.
        """
        if self.schedule != "chunked":
            return None
        if self.p1_chunk is not None:
            return self.p1_chunk
        return auto_chunk(L_bucket)

    def _bfs_engine(self, n_bucket: int) -> str:
        """Per-bucket BFS-engine policy.

        The engine is a compiled-program key, so — exactly like the
        phase-1 block size — it is resolved through this one hook from
        the bucket, and `warmup` resolves through the same hook: warmed
        programs are the ones traffic requests. The default policy is
        uniform ("doubling" everywhere: it is never more loop rounds
        than level-sync and collapses diameter-bound buckets to
        O(log n)); subclasses with measured per-size preferences can
        override on `n_bucket`.
        """
        return self.bfs_engine

    def _bucket(self, n: int, L: int) -> Tuple[int, int]:
        """The bucketing policy, from raw sizes — the single source both
        the request path (`bucket_key`) and `warmup` resolve through, so
        warmed programs are exactly the ones traffic requests."""
        return (
            max(next_pow2(int(n)), self.min_n_bucket),
            max(next_pow2(int(L)), self.min_L_bucket),
        )

    def bucket_key(self, g: Graph) -> Tuple[int, int]:
        """(n_bucket, L_bucket): pad targets rounded up to powers of two.

        Well-defined for edgeless graphs too: next_pow2 floors at 1, so
        a (n=1, m=0) request lands in the smallest bucket."""
        return self._bucket(g.n, g.m)

    def _b_cap(self, n_bucket: int, budgets: Sequence[int]) -> int:
        """Accept-buffer bucket for a chunk.

        Keyed off the bucket's own default budget so that default-budget
        traffic (every graph's budget <= default_budget(n_bucket)) maps
        to ONE compiled b_cap per shape bucket — which is also what
        `warmup` compiles. Larger explicit budgets widen it (and land a
        fresh dispatch signature: see n_on_path_compiles).
        """
        return _bucket_b_cap(list(budgets) + [default_budget(n_bucket)])

    def _program_kwargs(self, n_bucket: int, L_bucket: int,
                        b_cap: int) -> dict:
        """The static kwargs of the compiled program for one dispatch
        signature — the SINGLE definition `_dispatch`, `warmup` (via
        `_dispatch`) and the static auditor (`program_specs`) share, so
        what the auditor proves is exactly what traffic runs."""
        return dict(
            n=n_bucket,
            k_cap=self.k_cap,
            parallel=self.parallel,
            lift_levels=None,
            b_cap=b_cap,
            use_tree_kernel=False,
            chunk=32,
            schedule=self.schedule,
            p1_chunk=self._p1_chunk(L_bucket),
            use_euler_lca=True,
            bfs_engine=self._bfs_engine(n_bucket),
        )

    @property
    def dispatch_fn(self):
        """The ONE jit callable every device chunk dispatches through
        for this service's mode (donated or plain)."""
        return (lgrass_device_batched_donated if self.donate
                else lgrass_device_batched)

    def compiled_signatures(self) -> List[Tuple[int, int, int, int]]:
        """Every dispatch signature (n_bucket, L_bucket, B_pad, b_cap)
        this service has compiled — warmed and request-path alike."""
        return sorted(self._warmed | self._seen)

    def program_specs(
        self,
        sizes: Optional[Iterable[Tuple[int, int]]] = None,
        batch_sizes: Sequence[int] = (1,),
        budgets: Sequence[int] = (),
    ) -> List[ProgramSpec]:
        """`ProgramSpec`s for the compiled-program set, WITHOUT
        compiling or dispatching anything — pure bucketing math, so the
        static auditor can cover the warmed signature set off-device.

        sizes=None audits the signatures already compiled
        (`compiled_signatures`); otherwise (n, L) pairs are resolved
        through the same bucketing/b_cap/batch-pad policies `warmup`
        and the request path use.
        """
        if sizes is None:
            sigs = self.compiled_signatures()
        else:
            sigset = set()
            for (n, L) in sizes:
                n_bucket, L_bucket = self._bucket(n, L)
                b_cap = self._b_cap(n_bucket, list(budgets))
                for B in batch_sizes:
                    sigset.add((n_bucket, L_bucket, self._pad_batch(int(B)),
                                b_cap))
            sigs = sorted(sigset)
        mode = ("donated" if self.donate else
                "sharded" if self.mesh is not None else "plain")
        sh = None if self.mesh is None else batch_sharding(self.mesh)
        specs = []
        for sig in sigs:
            n_bucket, L_bucket, B_pad, b_cap = sig
            args = tuple(
                jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
                for shape, dtype in (((B_pad, L_bucket), jnp.int32),
                                     ((B_pad, L_bucket), jnp.int32),
                                     ((B_pad, L_bucket), jnp.float32),
                                     ((B_pad, L_bucket), jnp.bool_),
                                     ((B_pad,), jnp.int32)))
            specs.append(ProgramSpec(
                name=f"lgrass_device_batched[{mode}]"
                     f"(n={n_bucket},L={L_bucket},B={B_pad},b_cap={b_cap})",
                signature=sig,
                fn=self.dispatch_fn,
                args=args,
                static_kwargs=self._program_kwargs(n_bucket, L_bucket,
                                                   b_cap),
            ))
        return specs

    @property
    def _shards(self) -> int:
        """Row blocks of a dispatch: one per device of the mesh."""
        return 1 if self.mesh is None else mesh_size(self.mesh)

    def _pad_batch(self, n_chunk: int) -> int:
        """Batch-axis pad target for a chunk of `n_chunk` graphs: the
        next power of two, rounded up to whole mesh multiples when
        sharding so every shard gets equal rows."""
        if self.mesh is not None:
            ms = self._shards
            return ms * next_pow2(-(-int(n_chunk) // ms))
        return next_pow2(int(n_chunk))

    # ---------------------------------------------------------- dispatch

    def _dispatch(
        self,
        graphs: Sequence[Graph],
        budgets: Sequence[int],
        n_bucket: int,
        L_bucket: int,
        B_pad: int,
        b_cap: int,
    ) -> dict:
        """Enqueue ONE padded chunk on the device; returns the device
        output dict WITHOUT blocking (JAX dispatch is async). The single
        funnel for the request path AND warmup, so the donated/sharded
        program variants are exactly the ones warmup compiles."""
        with jax.profiler.TraceAnnotation("svc.stage"):
            entry = self._pool.acquire(B_pad, L_bucket)
            u, v, w, ev, bb = self._pool.fill(entry[0], graphs)
            bb[: len(budgets)] = np.asarray(budgets, np.int32)
            # jnp.array (copy=True) — NOT asarray/device_put, which
            # zero-copy aligned host buffers on CPU PJRT and would alias
            # the staging pool into live device arrays (see _StagingPool)
            arrs = (jnp.array(u), jnp.array(v), jnp.array(w),
                    jnp.array(ev), jnp.array(bb))
            if self.mesh is not None:
                with jax.profiler.TraceAnnotation("svc.shard"):
                    arrs = shard_batch_leading(arrs, self.mesh)
        with jax.profiler.TraceAnnotation("svc.dispatch"), \
                warnings.catch_warnings():
            # only edge_valid/budget can alias a same-shape output; XLA's
            # "donated buffers were not usable" note for u/v/w is expected
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            d = self.dispatch_fn(
                *arrs,
                **self._program_kwargs(n_bucket, L_bucket, b_cap),
            )
        # re-arm the fence: these outputs ready <=> this dispatch ran and
        # consumed its (async) input transfers => buffers reusable
        entry[1] = d["n_accepted"]
        return d

    def _drain(self, pending: _PendingChunk,
               results: List[Optional[SparsifyResult]]):
        """Block on one chunk's device outputs, scatter its rows into
        `results` at their request indices (placeholder tail dropped),
        and count its loop rounds."""
        with jax.profiler.TraceAnnotation("svc.drain"):
            host = jax.device_get(pending.device)
            for row, (i, L) in enumerate(zip(pending.idxs, pending.Ls)):
                results[i] = _result_from_device(host, row, L)
            self.stats.count_loops(host["loop_rounds"], len(pending.idxs),
                                   self._shards)

    # ---------------------------------------------------------- serving

    def _by_bucket(self, graphs: Sequence[Graph]
                   ) -> Dict[Tuple[int, int], List[int]]:
        """Request indices grouped by bucket, under the `svc.bucket`
        span."""
        by_bucket: Dict[Tuple[int, int], List[int]] = {}
        with jax.profiler.TraceAnnotation("svc.bucket"):
            for i, g in enumerate(graphs):
                by_bucket.setdefault(self.bucket_key(g), []).append(i)
        return by_bucket

    def sparsify(
        self,
        graphs: Sequence[Graph],
        budget: Optional[object] = None,
    ) -> List[SparsifyResult]:
        """Sparsify `graphs`, returning results in request order.

        budget: None (per-graph default), an int for all graphs, or a
        sequence with one budget per graph.
        """
        graphs = list(graphs)
        # same scalar/sequence normalization as lgrass_sparsify_batch
        if budget is None or np.ndim(budget) == 0:
            budgets = [budget] * len(graphs)
        else:
            budgets = list(budget)
            if len(budgets) != len(graphs):
                raise ValueError("one budget per graph required")

        by_bucket = self._by_bucket(graphs)

        results: List[Optional[SparsifyResult]] = [None] * len(graphs)
        pending: List[_PendingChunk] = []
        for key in sorted(by_bucket):
            idxs = by_bucket[key]
            n_bucket, L_bucket = key
            self.stats.bucket_counts[key] = (
                self.stats.bucket_counts.get(key, 0) + len(idxs)
            )
            for lo in range(0, len(idxs), self.max_batch_size):
                chunk = idxs[lo: lo + self.max_batch_size]
                B_pad = self._pad_batch(len(chunk))
                # resolve None budgets ONCE; the program receives concrete
                # values, so b_cap sizing and dispatch can't disagree
                resolved = [
                    default_budget(graphs[i].n) if budgets[i] is None
                    else int(budgets[i])
                    for i in chunk
                ]
                b_cap = self._b_cap(n_bucket, resolved)
                sig = (n_bucket, L_bucket, B_pad, b_cap)
                if sig not in self._warmed and sig not in self._seen:
                    self.stats.n_on_path_compiles += 1
                self._seen.add(sig)
                if self.recovery == "host":
                    out = self._sparsify_host_chunk(
                        graphs, chunk, resolved, n_bucket, L_bucket, B_pad,
                        b_cap, results)
                    self.stats.count_loops(
                        [[r.loop_rounds[k] for k in LOOPS] for r in out],
                        len(chunk))
                else:
                    d = self._dispatch(
                        [graphs[i] for i in chunk], resolved,
                        n_bucket, L_bucket, B_pad, b_cap)
                    item = _PendingChunk(
                        idxs=chunk, Ls=[graphs[i].m for i in chunk], device=d)
                    if self.async_dispatch:
                        pending.append(item)   # drain after ALL dispatches
                    else:
                        self._drain(item, results)
                n_fill = B_pad - len(chunk)
                n_real = sum(graphs[i].m for i in chunk)
                self.stats.n_dispatches += 1
                self.stats.n_graphs += len(chunk)
                self.stats.n_padded_edge_slots += L_bucket * B_pad
                self.stats.n_real_edge_slots += n_real
                self.stats.n_batch_pad_edge_slots += L_bucket * n_fill
                self.stats.n_shape_pad_edge_slots += (
                    L_bucket * len(chunk) - n_real
                )
        for item in pending:
            self._drain(item, results)
        return results  # type: ignore[return-value]

    def _sparsify_host_chunk(self, graphs, chunk, resolved, n_bucket,
                             L_bucket, B_pad, b_cap, results):
        """The oracle tail (recovery='host'): per-chunk blocking batch
        call through lgrass_sparsify_batch — kept for fidelity checks.
        Returns every row's result, placeholders included."""
        from repro.core.sparsify import lgrass_sparsify_batch

        n_fill = B_pad - len(chunk)
        batch = GraphBatch.from_graphs(
            [graphs[i] for i in chunk] + [_placeholder_graph()] * n_fill,
            n_max=n_bucket,
            L_max=L_bucket,
        )
        out = lgrass_sparsify_batch(
            batch,
            budget=list(resolved) + [None] * n_fill,
            k_cap=self.k_cap, parallel=self.parallel,
            recovery=self.recovery,
            b_cap=b_cap,
            schedule=self.schedule,
            p1_chunk=self._p1_chunk(L_bucket),
            bfs_engine=self._bfs_engine(n_bucket),
        )
        for i, r in zip(chunk, out):  # placeholder tail dropped
            results[i] = r
        return out

    def warmup(
        self,
        sizes: Iterable[Tuple[int, int]],
        batch_sizes: Sequence[int] = (1,),
        budgets: Sequence[int] = (),
    ) -> int:
        """Pre-compile bucket programs for anticipated request shapes.

        sizes: (n, L) pairs of representative requests — each is rounded
        to its bucket exactly as `sparsify` would. batch_sizes: chunk
        sizes to warm (each padded to the same batch-axis target as the
        request path — pow2, mesh-rounded when sharding). budgets:
        explicit request budgets to warm `b_cap` buckets for — without
        this, only the bucket-default b_cap program is compiled, and a
        request with a larger explicit budget costs an on-path compile
        (counted in `stats.n_on_path_compiles`). Dispatches run on
        placeholder graphs whose results are discarded; XLA's compile
        cache then serves real traffic without on-path compilation.
        Warmup goes through the SAME dispatch funnel as traffic, so the
        donated / sharded program variants are warmed when those modes
        are on. Returns the number of warmup dispatches;
        `stats.n_warmup_dispatches` / `stats.warmup_seconds` accumulate.
        """
        t0 = time.perf_counter()
        n_dispatched = 0
        for (n, L) in sizes:
            n_bucket, L_bucket = self._bucket(n, L)
            b_cap = self._b_cap(n_bucket, list(budgets))
            for B in batch_sizes:
                B_pad = self._pad_batch(int(B))
                sig = (n_bucket, L_bucket, B_pad, b_cap)
                if sig in self._warmed:
                    continue
                self._warmed.add(sig)
                if self.recovery == "host":
                    out: List[Optional[SparsifyResult]] = [None] * B_pad
                    self._sparsify_host_chunk(
                        [_placeholder_graph()] * B_pad, list(range(B_pad)),
                        [1] * B_pad, n_bucket, L_bucket, B_pad, b_cap, out)
                else:
                    d = self._dispatch(
                        [_placeholder_graph()] * B_pad, [1] * B_pad,
                        n_bucket, L_bucket, B_pad, b_cap)
                    jax.block_until_ready(d)  # compile NOW, off-path
                n_dispatched += 1
        self.stats.n_warmup_dispatches += n_dispatched
        self.stats.warmup_seconds += time.perf_counter() - t0
        return n_dispatched
