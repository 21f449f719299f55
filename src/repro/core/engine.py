"""PSelInvEngine — the analyze/plan/bind/solve session API.

The paper's central observation is that the *structure* of the
restricted collectives (trees, rounds, tables) is fully known before a
single value moves. Production selected-inversion libraries split their
API exactly there (PSelInv's ``SymbolicFactorize``/``NumericalSelInv``,
Serinv's symbolic setup vs repeated numeric solves); this module is that
split for the JAX reproduction:

    engine = PSelInvEngine.analyze(A_or_structure, b=8,
                                   grid=Grid(4, 2),
                                   options=PlanOptions(...))
    out = engine.solve(values)            # value-only hot path

``analyze`` performs symbolic analysis → CommPlan IR → (overlapped)
round schedule → per-device gather/scatter tables → the jitted
shard_map sweep **once**, and caches the whole session keyed on
(block-structure hash, supernode width, grid, :class:`PlanOptions`) —
a second ``analyze`` with an identical structure returns the *same*
engine, compiled program included. ``solve`` moves values only: the
host numeric factorization (when given a matrix) plus one call of the
cached jitted sweep — no symbolic work, no re-lowering, no retrace. A
matrix the engine factorizes itself crosses to the device as its
factor's structural blocks alone (:class:`PackedValues`), which a
small compiled program unpacks there into the sweep's dense inputs.

**Multi-matrix batching** comes from the same structure/value split:
the compiled tables are value-independent, so ``solve`` accepts a
leading batch axis (``values`` shaped (B, P, nbr, nbc, b, b)) and runs
all B matrices through one ``vmap``-ed sweep — one trace, one compile,
B results (``solve_many`` stacks a list of matrices for you). This is
the ROADMAP's "many matrices, same structure" serving path.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import ClassVar, Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..compat import shard_map
from ..obs import compiles
from ..obs.registry import REGISTRY
from ..obs.trace import TRACER
from .plan import (PlanOptions, peak_arena_blocks, ppermute_round_count)
from .pselinv_dist import (PackTables, PSelInvProgram, analyze_structure,
                           build_program, check_grid_devices,
                           make_sweep_overlapped, make_sweep_stream,
                           pack_tables, pad_nb, prepare_packed_many,
                           prepare_values, prepare_values_many,
                           validate_uniform_widths)
from .schedule import BYTES_PER_ELT, Grid2D
from .symbolic import BlockStructure

__all__ = ["Grid", "PlanOptions", "PSelInvEngine", "SolveValues",
           "PackedValues", "structure_key", "stack_values", "bucket_size"]

# jax.compile spans for every trace / lower / compile while TRACER is on
compiles.install()

#: the session API's name for the 2-D process grid (one definition —
#: ``schedule.Grid2D`` — reused, not duplicated)
Grid = Grid2D


class SolveValues(NamedTuple):
    """One matrix's numeric payload in device layout: ``Lh`` and ``Dinv``
    shaped (P, nbr, nbc, b, b) — or (B, P, nbr, nbc, b, b) with a
    leading batch axis for multi-matrix solves."""
    Lh: np.ndarray
    Dinv: np.ndarray


class PackedValues(NamedTuple):
    """One matrix's numeric payload as only its structural blocks — the
    f64 lists of :class:`~.pselinv_dist.PackTables`: ``Lh`` shaped
    (P, nL, b, b) and ``Dinv`` (P, nD, b, b), or with a leading batch
    axis. The engine's own factorization emits them
    (:meth:`PSelInvEngine.prepare_packed`); :meth:`PSelInvEngine
    .to_device` ships them and rebuilds the dense shards on the
    device."""
    Lh: np.ndarray
    Dinv: np.ndarray


def stack_values(values: Sequence[SolveValues]) -> SolveValues:
    """Stack per-matrix :class:`SolveValues` along a new leading batch
    axis (same structure, many matrices)."""
    return SolveValues(np.stack([v.Lh for v in values]),
                       np.stack([v.Dinv for v in values]))


def structure_key(bs: BlockStructure) -> str:
    """Content hash of a block structure — the value-independent part of
    the engine cache key. Two matrices with equal sparsity structure
    (same supernodes, same fill, same etree) hash equal and share one
    compiled session."""
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(bs.offsets, dtype=np.int64).tobytes())
    h.update(np.ascontiguousarray(bs.parent, dtype=np.int64).tobytes())
    for s in bs.struct:
        h.update(np.ascontiguousarray(s, dtype=np.int64).tobytes())
        h.update(b"|")
    return h.hexdigest()


def bucket_size(B: int) -> int:
    """The padded batch bucket for B matrices: the next power of two.

    Each distinct batch length traces (and XLA-compiles) its own vmapped
    sweep, so a serving workload with organic batch sizes 3, 5, 13, …
    would retrace per length. Rounding up to power-of-2 buckets bounds
    the program population at log₂(max batch) per structure — a burst of
    13 rides the B=16 program (pad lanes carry zeros and are sliced off
    the result)."""
    if B < 1:
        raise ValueError(f"batch size must be >= 1, got {B}")
    return 1 << (B - 1).bit_length()


def _approx_nbytes(obj, _seen=None, _depth=0) -> int:
    """Approximate resident bytes of a program/table object: the sum of
    every reachable numpy array's ``nbytes`` (dataclasses, dicts, lists,
    tuples walked; shared arrays counted once). The engine cache's
    size-aware eviction bound runs on this — an *approximation* is fine,
    the arrays dominate and python-object overhead is noise."""
    if _seen is None:
        _seen = set()
    if _depth > 16 or id(obj) in _seen:
        return 0
    if isinstance(obj, np.ndarray):
        _seen.add(id(obj))
        return int(obj.nbytes)
    if isinstance(obj, (str, bytes, int, float, bool, complex,
                        type(None))):
        return 0
    _seen.add(id(obj))
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return sum(_approx_nbytes(getattr(obj, f.name), _seen, _depth + 1)
                   for f in dataclasses.fields(obj))
    if isinstance(obj, dict):
        return sum(_approx_nbytes(v, _seen, _depth + 1)
                   for v in obj.values())
    if isinstance(obj, (list, tuple, set, frozenset)):
        return sum(_approx_nbytes(v, _seen, _depth + 1) for v in obj)
    return 0


def _is_matrix(x) -> bool:
    """A numeric matrix (dense 2-D array or scipy sparse) as opposed to
    prepared value shards."""
    try:
        import scipy.sparse as sp
        if sp.issparse(x):
            return True
    except ImportError:                       # pragma: no cover
        pass
    return hasattr(x, "ndim") and getattr(x, "ndim", 0) == 2


@dataclass
class PSelInvEngine:
    """One compiled selected-inversion session: structure + grid +
    options bound to a jitted sweep. Construct through
    :meth:`analyze` — the constructor itself performs no work."""
    bs: BlockStructure
    b: int
    nb: int
    grid: Grid2D
    options: PlanOptions
    program: PSelInvProgram
    mesh: object
    key: Tuple = ()
    #: times the jitted sweep body was (re)traced — regression handle for
    #: the "solve does not retrace" contract
    trace_count: int = 0
    solve_calls: int = 0
    _fns: Dict[bool, object] = field(default_factory=dict)
    _compile_metrics: Dict[Tuple, Dict[str, float]] = \
        field(default_factory=dict, repr=False)
    _hlo_lint: Dict[Tuple, list] = field(default_factory=dict,
                                         repr=False)
    _jit_lock: threading.Lock = field(default_factory=threading.Lock,
                                      repr=False)
    _round_schedule: Optional[object] = None
    _table_bytes: Optional[int] = field(default=None, repr=False)
    _comm: Optional[Dict[str, float]] = field(default=None, repr=False)
    _pack: Optional[PackTables] = field(default=None, repr=False)
    _unpackers: Dict[Tuple, object] = field(default_factory=dict,
                                            repr=False)
    #: batches :meth:`to_device` shipped as structural blocks / dense
    h2d_packed_batches: int = 0
    h2d_dense_batches: int = 0

    # ---- the structure cache (class-level, all sessions) --------------
    _cache: ClassVar["OrderedDict[Tuple, PSelInvEngine]"] = OrderedDict()
    _cache_lock: ClassVar[threading.Lock] = threading.Lock()
    #: LRU eviction bounds — a long-lived server analyzing a stream of
    #: distinct structures must not pin every session's tables and
    #: compiled executables for process lifetime. A cache *hit* moves
    #: the session to the back of the queue, so the structures real
    #: traffic keeps re-hitting stay resident (the serving layer's warm
    #: engines) while one-off structures age out the front.
    #: ``cache_max`` bounds the session count; ``cache_max_bytes``
    #: bounds the summed per-engine table footprint
    #: (:meth:`table_bytes`) — the real production bound, since table
    #: bytes vary ~nb²·b² per structure while the count does not. The
    #: most-recently-inserted session is never evicted, so a single
    #: over-budget structure still solves.
    cache_max: ClassVar[int] = 16
    cache_max_bytes: ClassVar[int] = 1 << 30
    cache_hits: ClassVar[int] = 0
    cache_misses: ClassVar[int] = 0
    cache_evictions: ClassVar[int] = 0

    @classmethod
    def analyze(cls, structure_or_A, b: int, grid: Grid2D,
                options: PlanOptions = PlanOptions(), *,
                verify: str | None = None,
                verify_compiled: str | None = None) -> "PSelInvEngine":
        """Symbolic analysis → CommPlan → schedule → tables → jitted
        sweep, **once per structure**. Accepts a matrix (symbolically
        factorized here) or a ready :class:`BlockStructure`; returns the
        cached engine when an identical (structure, b, grid, options)
        session already exists.

        ``verify`` overrides ``options.verify`` — the PlanLint mode
        (``"error"`` | ``"warn"`` | ``"off"``) applied to the lowered
        program at build time. ``verify_compiled`` likewise overrides
        ``options.verify_compiled`` — the HloLint mode applied to the
        compiled jaxpr/StableHLO layers of the program's own sweep
        (``core/hlo_verify.py``; traced on an abstract mesh at build
        time). Both are part of the cache key (two sessions that differ
        only in verification mode compile independently)."""
        check_grid_devices(grid.pr, grid.pc)
        if verify is not None:
            options = dataclasses.replace(options, verify=verify)
        if verify_compiled is not None:
            options = dataclasses.replace(options,
                                          verify_compiled=verify_compiled)
        with TRACER.span("engine.analyze", b=b,
                         grid=f"{grid.pr}x{grid.pc}") as sp:
            if isinstance(structure_or_A, BlockStructure):
                bs = structure_or_A
                validate_uniform_widths(bs, b)
                nb = pad_nb(bs.nsuper, grid.pr, grid.pc)
            else:
                with TRACER.span("analyze.symbolic"):
                    bs, nb = analyze_structure(structure_or_A, b,
                                               grid.pr, grid.pc)
            sp.set(nb=nb)

            key = (structure_key(bs), b, grid, options)
            with cls._cache_lock:
                hit = cls._cache.get(key)
                if hit is not None:
                    cls.cache_hits += 1
                    cls._cache.move_to_end(key)  # LRU: a hit stays warm
                    sp.set(cache="hit")
                    return hit
                cls.cache_misses += 1
            sp.set(cache="miss")

            from jax.sharding import Mesh
            program = build_program(bs, nb, b, grid.pr, grid.pc,
                                    options=options)
            devs = np.array(jax.devices()[:grid.size]).reshape(grid.size)
            engine = cls(bs=bs, b=b, nb=nb, grid=grid, options=options,
                         program=program, mesh=Mesh(devs, ("xy",)),
                         key=key)
            engine.comm_counters()
            engine.pack_tables()
        with cls._cache_lock:
            # somebody may have raced us past the miss above; keep the
            # first published session so `analyze` stays idempotent
            engine = cls._cache.setdefault(key, engine)
            cls._cache.move_to_end(key)
            cls._evict_locked()
        return engine

    @classmethod
    def _evict_locked(cls) -> None:
        """LRU eviction under ``_cache_lock``: pop the front while the
        session count exceeds ``cache_max`` or the summed table bytes
        exceed ``cache_max_bytes`` — keeping at least the most recent
        session so one over-budget structure still solves."""
        def over():
            if len(cls._cache) > cls.cache_max:
                return True
            return sum(e.table_bytes()
                       for e in cls._cache.values()) > cls.cache_max_bytes
        while len(cls._cache) > 1 and over():
            cls._cache.popitem(last=False)
            cls.cache_evictions += 1

    @classmethod
    def cache_bytes(cls) -> int:
        """Summed approximate table bytes of every cached session (the
        quantity ``cache_max_bytes`` bounds)."""
        with cls._cache_lock:
            return sum(e.table_bytes() for e in cls._cache.values())

    @classmethod
    def clear_cache(cls) -> None:
        with cls._cache_lock:
            cls._cache.clear()
            cls.cache_hits = cls.cache_misses = 0
            cls.cache_evictions = 0

    # ---- lowering / jit (once per (batched, dtype) shape class) -------
    def _shard_mapped_sweep(self, batched: bool, counted: bool):
        """The session's sweep (per its :class:`PlanOptions` executor)
        wrapped for shard_map — the one builder :meth:`jitted` and
        :meth:`compile_stats` share. ``counted=True`` wraps the body so
        each (re)trace bumps ``trace_count`` (the no-retrace regression
        handle); measurement paths pass False so they never touch the
        counter."""
        from jax.sharding import PartitionSpec as P
        mk = (make_sweep_stream if self.options.stream
              else make_sweep_overlapped)
        sweep = mk(self.program, batched=batched)
        if counted:
            inner = sweep

            def sweep(Lh, Dinv):
                self.trace_count += 1         # fires at trace time only
                return inner(Lh, Dinv)

        spec = P(None, "xy") if batched else P("xy")
        return shard_map(sweep, mesh=self.mesh,
                         in_specs=(spec, spec), out_specs=spec)

    def jitted(self, batched: bool = False):
        """The compiled shard_map sweep as a ``jax.jit`` callable.
        Single-matrix signature: (Lh, Dinv) each (P, nbr, nbc, b, b),
        sharded over mesh axis "xy". Batched: (B, P, nbr, nbc, b, b) —
        the leading axis is vmapped through the value tensors while the
        static tables are shared (no per-item retrace)."""
        with self._jit_lock:     # cached sessions are shared: one
            fn = self._fns.get(batched)      # builder per shape class
            if fn is None:
                fn = jax.jit(self._shard_mapped_sweep(batched,
                                                      counted=True))
                self._fns[batched] = fn
        return fn

    # ---- the value-only hot path --------------------------------------
    def prepare_values(self, A, dtype=None) -> SolveValues:
        """Numeric host factorization of one matrix against the cached
        structure → device-layout shards (the batched pass at B=1,
        traced as ``engine.prepare_values``). No symbolic work."""
        with TRACER.span("engine.prepare_values"):
            Lh, Dinv = prepare_values(A, self.bs, self.nb, self.b,
                                      self.grid.pr, self.grid.pc)
            if dtype is not None:
                Lh, Dinv = Lh.astype(dtype), Dinv.astype(dtype)
        return SolveValues(Lh, Dinv)

    def prepare_values_many(self, mats: Sequence,
                            dtype=None) -> SolveValues:
        """Batched numeric host factorization of B same-structure
        matrices → stacked (B, P, nbr, nbc, b, b) shards in one
        structure-driven pass (:func:`~.pselinv_dist
        .prepare_values_many`) — the supernode loop runs once with
        (B, b, b) block stacks and does each supernode's arithmetic as
        a few f64 BLAS-3 calls per matrix: one block inverse and two
        GEMMs, the larger of them the whole Schur update of the
        supernode's clique. The serving layer's host half of the
        coalescing win."""
        with TRACER.span("engine.prepare_values_many", B=len(mats)):
            Lh, Dinv = prepare_values_many(mats, self.bs, self.nb,
                                           self.b, self.grid.pr,
                                           self.grid.pc)
            if dtype is not None:
                Lh, Dinv = Lh.astype(dtype), Dinv.astype(dtype)
        return SolveValues(Lh, Dinv)

    def prepare_packed(self, mats: Sequence) -> PackedValues:
        """The batched host factorization of :meth:`prepare_values_many`
        (same span, same f64 blocks) emitting only the structural blocks:
        (B, P, n, b, b) :class:`PackedValues` for :meth:`to_device`."""
        with TRACER.span("engine.prepare_values_many", B=len(mats)):
            return PackedValues(*prepare_packed_many(
                mats, self.bs, self.nb, self.b, self.pack_tables()))

    def pack_tables(self) -> PackTables:
        """Where each structural block of L̂ and D⁻¹ sits in this
        session's device layout (:class:`~.pselinv_dist.PackTables`),
        made once per session (:meth:`analyze` calls it)."""
        if self._pack is None:
            self._pack = pack_tables(self.bs, self.nb, self.grid.pr,
                                     self.grid.pc)
        return self._pack

    def sharding(self, batched: bool):
        """The placement of the sweep's value inputs on this session's
        mesh: shards over "xy", after the batch axis when ``batched``."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh,
                             P(None, "xy") if batched else P("xy"))

    def to_device(self, values, dtype=None, bucket: Optional[int] = None):
        """Move one solve's values to the device as the sweep's dense
        ``(Lh, Dinv)`` inputs, placed by :meth:`sharding` (arrays already
        placed so stay as they are); ``bucket`` zero-pads the batch axis
        up to it, ``dtype`` casts (None keeps theirs).

        :class:`PackedValues` are cast on the host, padded to the bucket
        there, sent in one ``device_put`` and unpacked into zeroed dense
        shards by :meth:`unpacker`'s program, each device filling its
        own. Any other pair of arrays is cast and placed whole, and
        padded on the device. Traced as ``engine.h2d`` (``B``,
        ``packed`` 1/0, ``blocks`` per lane, ``bytes`` sent): while
        ``TRACER`` is enabled the span ends when the arrays are on the
        device, so it times the transfer and not its dispatch; disabled,
        nothing waits. Counted in ``h2d_packed_batches`` /
        ``h2d_dense_batches``."""
        Lh, Dinv = values
        if not isinstance(values, PackedValues):
            self.h2d_dense_batches += 1
            return self._dense_to_device(Lh, Dinv, dtype, bucket)
        self.h2d_packed_batches += 1
        batched = Lh.ndim == 5
        B = Lh.shape[0] if batched else 1
        lanes = bucket or B
        dt = jax.dtypes.canonicalize_dtype(dtype or Lh.dtype)
        with TRACER.span("engine.h2d", B=B, packed=1,
                         blocks=self.pack_tables().blocks) as sp:
            host = []
            for x in (Lh, Dinv):
                if lanes == B:
                    host.append(np.asarray(x, dtype=dt))
                else:
                    pad = np.zeros((lanes,) + x.shape[1:], dtype=dt)
                    pad[:B] = x
                    host.append(pad)
            sent = jax.device_put(tuple(host), self.sharding(batched))
            out = self.unpacker(lanes if batched else None, dt)(*sent)
            if TRACER.enabled:
                jax.block_until_ready(out)
                sp.set(bytes=int(sum(x.nbytes for x in host)))
        return out

    def _dense_to_device(self, Lh, Dinv, dtype, bucket):
        B = Lh.shape[0] if Lh.ndim == 6 else 1
        sharding = self.sharding(Lh.ndim == 6)
        with TRACER.span("engine.h2d", B=B, packed=0,
                         blocks=int(np.prod(Lh.shape[-5:-2])) * 2) as sp:
            # device arrays cross nothing; the pad lanes are made there
            host = [not isinstance(x, jax.Array) for x in (Lh, Dinv)]
            Lh = jnp.asarray(Lh, dtype=dtype, device=sharding)
            Dinv = jnp.asarray(Dinv, dtype=dtype, device=sharding)
            sent = sum(x.nbytes for x, h in zip((Lh, Dinv), host) if h)
            if bucket is not None and bucket != B:
                pad = ((0, bucket - B),) + ((0, 0),) * (Lh.ndim - 1)
                Lh, Dinv = jnp.pad(Lh, pad), jnp.pad(Dinv, pad)
            if TRACER.enabled:
                jax.block_until_ready((Lh, Dinv))
                sp.set(bytes=int(sent))
        return Lh, Dinv

    def unpacker(self, lanes: Optional[int], dtype):
        """The compiled program that turns shipped :class:`PackedValues`
        into the sweep's dense inputs: one ``shard_map`` over the mesh in
        which each device gathers its own shard's listed blocks into the
        dense ``(…, nbr, nbc, b, b)`` grid, zero where nothing is listed
        (an out-of-range take, filled). ``lanes`` is the batch bucket,
        None for one matrix. AOT-compiled once per (lanes, dtype) and
        kept on the session; uncounted (``trace_count`` is the sweep's)."""
        dtype = jax.dtypes.canonicalize_dtype(dtype)
        key = (lanes, dtype.name)
        with self._jit_lock:
            comp = self._unpackers.get(key)
        if comp is not None:
            return comp
        t = self.pack_tables()
        b, nbr, nbc = self.b, t.nbr, t.nbc

        def unpack(Lh, Dinv):
            idx = jax.lax.axis_index("xy")

            def one(x, take):
                lead = x.shape[:-3]           # (1,) or (lanes, 1)
                x = x.reshape((-1,) + x.shape[-3:])
                g = jnp.take(jnp.asarray(take), idx, axis=0)
                out = jnp.take(x, g, axis=1, mode="fill", fill_value=0)
                return out.reshape(lead + (nbr, nbc, b, b))
            return one(Lh, t.lh_take), one(Dinv, t.d_take)

        batched = lanes is not None
        sh = self.sharding(batched)
        spec = sh.spec
        lead = (lanes,) if batched else ()
        fn = jax.jit(shard_map(unpack, mesh=self.mesh, in_specs=(spec, spec),
                               out_specs=(spec, spec)),
                     out_shardings=(sh, sh))   # as the sweep was warmed
        comp = fn.trace(*(jax.ShapeDtypeStruct(lead + pos.shape + (b, b),
                                               dtype, sharding=sh)
                          for pos in (t.lh_pos, t.d_pos))).lower().compile()
        with self._jit_lock:
            return self._unpackers.setdefault(key, comp)

    def solve(self, values, dtype=jnp.float32, *, bucket: bool = False):
        """Selected inversion of one matrix — or a whole batch.

        ``values`` is a matrix (numeric-factorized here against the
        cached structure and shipped as :class:`PackedValues`), a
        :class:`PackedValues`, a :class:`SolveValues`, or a plain
        ``(Lh, Dinv)`` pair. Arrays of rank 5 ((P, nbr, nbc, b, b);
        packed, rank 4) solve one matrix; rank 6 ((B, P, nbr, nbc, b,
        b), the leading **batch axis**; packed, rank 5) solve B
        same-structure matrices through one vmapped sweep call. Returns
        the A⁻¹ shards in the dense layout (rank 5 or 6). ``dtype``
        casts the values (f32 default); pass ``None`` to keep the
        arrays' own dtype.

        ``bucket=True`` pads a batched solve up to the next power-of-2
        bucket (:func:`bucket_size`) with zero-valued lanes and slices
        the real results back out — every distinct batch length
        otherwise traces and compiles its own program, while bucketed
        batches of 3, 5, 13 all ride the B∈{4, 8, 16} programs (the
        serving layer's retrace bound)."""
        if _is_matrix(values):
            with TRACER.span("engine.prepare_values"):
                Lh, Dinv = prepare_packed_many([values], self.bs, self.nb,
                                               self.b, self.pack_tables())
            values = PackedValues(Lh[0], Dinv[0])
        # packed blocks carry one axis fewer than the dense shards
        rank = np.ndim(values[0]) + isinstance(values, PackedValues)
        if rank not in (5, 6):
            raise ValueError(
                f"values must be rank 5 (single) or rank 6 (leading "
                f"batch axis), got shape {np.shape(values[0])}")
        self.solve_calls += 1
        batched = rank == 6
        B = np.shape(values[0])[0] if batched else 1
        with TRACER.span("engine.solve", B=B) as sp:
            Lh, Dinv = self.to_device(values, dtype,
                                      bucket_size(B) if batched and bucket
                                      else None)
            out = self.jitted(batched=batched)(Lh, Dinv)
            if TRACER.enabled:
                # per element shipped: the solve's dtype, every lane
                lanes = Lh.shape[0] if batched else 1
                scale = lanes * Lh.dtype.itemsize / BYTES_PER_ELT
                sp.set(**{k: v if k == "rounds" else v * scale
                          for k, v in self.comm_counters().items()})
            if batched and Lh.shape[0] != B:
                out = out[:B]
        return out

    def solve_many(self, mats: Sequence, dtype=jnp.float32, *,
                   bucket: bool = False):
        """Convenience: numeric-factorize the same-structure matrices in
        one stacked :meth:`prepare_packed` pass and run ONE batched
        solve; ``bucket`` pads the batch to its power-of-2 bucket so odd
        batch lengths share compiled programs."""
        return self.solve(self.prepare_packed(mats), dtype=dtype,
                          bucket=bucket)

    def comm_counters(self) -> Dict[str, float]:
        """The restricted collectives of one single-matrix sweep, read
        off the cached plan once per session (:meth:`analyze` calls it):
        ``rounds``, the sweep's ppermute rounds; ``wire_bytes``, what its
        permutes ship over all devices, padding included
        (:func:`~.simulator.executed_wire_bytes`); ``recv_bytes_max`` and
        ``recv_bytes_mean``, the bytes one device receives, from the
        plan's trees over every op kind
        (:func:`~.simulator.volumes_from_plan`). Bytes are priced at
        ``BYTES_PER_ELT`` per element, as the simulator prices them.
        While tracing, :meth:`solve` stamps them on its ``engine.solve``
        span, the bytes scaled to its dtype and lanes."""
        if self._comm is None:
            from .simulator import executed_wire_bytes, volumes_from_plan
            _, inc = volumes_from_plan(self.program.plan)
            recv = sum(inc.values(), np.zeros(self.grid.size))
            self._comm = {
                "rounds": ppermute_round_count(self.program.overlap_plan),
                "wire_bytes": executed_wire_bytes(self.program),
                "recv_bytes_max": float(recv.max()),
                "recv_bytes_mean": float(recv.mean())}
        return self._comm

    def table_bytes(self) -> int:
        """Approximate resident bytes of this session's compiled tables
        (every numpy array reachable from the program object, counted
        once). Computed once and cached — the LRU cache's size-aware
        eviction bound (``cache_max_bytes``) sums this across
        sessions."""
        if self._table_bytes is None:
            self._table_bytes = _approx_nbytes(self.program)
        return self._table_bytes

    def aot_compile(self, batch_size: int = 1, dtype=jnp.float32, *,
                    batched: bool = True):
        """AOT trace → lower → XLA-compile the session's sweep for one
        exact shape class and hand back the ``jax.stages.Compiled``
        executable (*uncounted*: the no-retrace regression handle
        ``trace_count`` never moves). This is the serialization seam the
        serving layer's on-disk program cache
        (``repro.serve.progcache``) builds on — a compiled executable
        can be serialized, persisted, and reloaded after a restart
        without re-tracing or re-compiling the hot structure."""
        shape = ((int(batch_size),) if batched else ()) + (
            self.grid.size, self.nb // self.grid.pr,
            self.nb // self.grid.pc, self.b, self.b)
        sd = jax.ShapeDtypeStruct(shape, dtype)
        fn = jax.jit(self._shard_mapped_sweep(batched, counted=False))
        return fn.trace(sd, sd).lower().compile()

    # ---- plan introspection (no re-lowering) --------------------------
    def round_schedule(self):
        """The cached program's executed :class:`~.simulator.RoundSchedule`
        (built once, then reused — nothing is re-lowered)."""
        if self._round_schedule is None:
            from .simulator import round_schedule_of
            self._round_schedule = round_schedule_of(self.program)
        return self._round_schedule

    def simulate(self, model=None):
        """α-β timing of the cached compiled schedule
        (:func:`~.simulator.simulate_schedule` on :meth:`round_schedule`
        — replaces the hand-wired ``round_schedule_from_*`` plumbing)."""
        from .simulator import simulate_schedule
        return simulate_schedule(self.round_schedule(), model)

    def compile_stats(self, batched: bool = False, dtype=jnp.float32,
                      batch_size: int = 1) -> Dict[str, float]:
        """Compile metrics of the session's sweep program, measured once
        per (batched, dtype, batch size) shape class and cached:
        ``trace_lower_ms`` (trace + StableHLO lowering wall time),
        ``compile_ms`` (XLA compile wall time), ``jaxpr_lines`` (traced
        program size), ``hlo_bytes`` (lowered HLO text size),
        ``ppermute_count`` (collective-permute ops in the optimized HLO
        XLA actually runs) and ``collective_bytes`` (their per-device
        traffic priced with while-loop trip counts —
        ``core/hlo_ir.collective_bytes``). This is
        how the uniform round-stream's program-size win over the
        unrolled overlapped executor is inspected without running the
        bench — the stream's jaxpr/HLO no longer grow with the round
        count. Uses abstract ``ShapeDtypeStruct`` inputs: no values move, but trace,
        lowering and XLA compilation really run (seconds, not
        microseconds). Pass the ``batched``/``dtype``/``batch_size``
        your solves use to measure that exact shape class (jit
        specializes on all three). Measures a fresh *uncounted* build of
        the same program, so the no-retrace regression handle
        (``trace_count``) is never touched — even when solves run
        concurrently on the shared session."""
        key = (batched, jnp.dtype(dtype).name,
               int(batch_size) if batched else 1)
        with self._jit_lock:
            m = self._compile_metrics.get(key)
        if m is not None:
            return m
        shape = ((int(batch_size),) if batched else ()) + (
            self.grid.size, self.nb // self.grid.pr,
            self.nb // self.grid.pc, self.b, self.b)
        sd = jax.ShapeDtypeStruct(shape, dtype)
        fn = jax.jit(self._shard_mapped_sweep(batched, counted=False))
        # the AOT path traces ONCE and hands back jaxpr + lowering
        t0 = time.perf_counter()
        with TRACER.span("engine.trace_lower", batched=batched):
            traced = fn.trace(sd, sd)
            lowered = traced.lower()
        t_lower = time.perf_counter() - t0
        jaxpr_lines = len(str(traced.jaxpr).splitlines())
        hlo_bytes = len(lowered.as_text())
        t0 = time.perf_counter()
        with TRACER.span("engine.compile", batched=batched):
            compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
        # compiled-collective census off the optimized HLO (the program
        # XLA actually runs): permute op count and per-device collective
        # traffic priced with while-loop trip counts
        from . import hlo_ir
        compiled_txt = compiled.as_text()
        ppermute_count = sum(
            1 for op in hlo_ir.parse_collectives(compiled_txt)
            if op.op == "collective-permute")
        coll_bytes = float(sum(
            hlo_ir.collective_bytes(compiled_txt).values()))
        m = {"trace_lower_ms": t_lower * 1e3,
             "compile_ms": t_compile * 1e3,
             "jaxpr_lines": jaxpr_lines,
             "hlo_bytes": hlo_bytes,
             "ppermute_count": ppermute_count,
             "collective_bytes": coll_bytes}
        with self._jit_lock:
            m = self._compile_metrics.setdefault(key, m)
        return m

    def lint_compiled(self, batched: bool = False, dtype=jnp.float32,
                      batch_size: int = 1, *, verify_compiled:
                      str | None = None):
        """HloLint the session's compiled sweep at **all three layers**
        — traced jaxpr, lowered StableHLO, and the optimized HLO of a
        real XLA compile (``core/hlo_verify.py``; cross-checks permute
        conformance, loop trip counts, wire-byte conservation and
        hot-path hygiene against the session's own plan tables).
        Measured once per (batched, dtype, batch size) shape class and
        cached. ``verify_compiled`` applies an enforcement mode to the
        result (``"error"`` raises
        :class:`~.verify.PlanVerificationError` on any ERROR
        diagnostic, ``"warn"`` warns once, default ``None`` just
        returns the diagnostics)."""
        from . import hlo_verify
        from .verify import enforce_verification

        key = (batched, jnp.dtype(dtype).name,
               int(batch_size) if batched else 1)
        with self._jit_lock:
            diags = self._hlo_lint.get(key)
        if diags is None:
            shape = ((int(batch_size),) if batched else ()) + (
                self.grid.size, self.nb // self.grid.pr,
                self.nb // self.grid.pc, self.b, self.b)
            sd = jax.ShapeDtypeStruct(shape, dtype)
            fn = jax.jit(self._shard_mapped_sweep(batched,
                                                  counted=False))
            traced = fn.trace(sd, sd)
            lowered = traced.lower()
            batch = int(batch_size) if batched else 1
            diags = (hlo_verify.lint_jaxpr(traced.jaxpr, self.program,
                                           batch=batch)
                     + hlo_verify.lint_text(lowered.as_text(),
                                            self.program, batch=batch,
                                            layer="stablehlo")
                     + hlo_verify.lint_text(lowered.compile().as_text(),
                                            self.program, batch=batch,
                                            layer="hlo"))
            with self._jit_lock:
                diags = self._hlo_lint.setdefault(key, diags)
        if verify_compiled is not None:
            enforce_verification(
                diags, mode=verify_compiled,
                where=f"compiled sweep (nb={self.nb}, "
                      f"grid={self.grid.pr}x{self.grid.pc})")
        return diags

    def profile_rounds(self, values, *, chunk: int = 1, reps: int = 3,
                       dtype=jnp.float32, model=None):
        """Measured per-round timeline of this session's sweep: re-runs
        the overlapped schedule as per-round jitted segments with
        ``block_until_ready`` fencing and joins the walls against the
        plan's wire tables — residuals vs the α-β simulator, the
        per-rank inbound skew report, and best-fit α/β estimates.
        Returns a :class:`~repro.obs.rounds.RoundProfile`; see
        :func:`repro.obs.rounds.profile_rounds` for the knobs
        (``chunk`` coarsens to level-chunk segments, ``reps`` keeps the
        per-segment minimum). The replay runs the *same* device code as
        the fused sweep (bit-identical result, tested), so the timeline
        is a measurement, not an estimate."""
        from ..obs.rounds import profile_rounds
        return profile_rounds(self, values, chunk=chunk, reps=reps,
                              dtype=dtype, model=model)

    def stats(self, compile: bool = False) -> Dict[str, float]:
        """Static schedule metrics of the cached program: ppermute round
        count, the other :meth:`comm_counters` (``wire_bytes``,
        ``recv_bytes_max``, ``recv_bytes_mean``, at ``BYTES_PER_ELT``
        per element) and peak per-device arena footprint (blocks). Stream
        sessions additionally report their executed wire traffic —
        ``stream_wire_bytes`` (physical permute bytes per sweep from the
        gated slot tables, padding included) and
        ``stream_shifts_per_round`` (mean gated permutes executed per
        comm round) — the two numbers the grid-factored encoding exists
        to shrink. ``compile=True`` additionally reports compile metrics
        for the f32 single-matrix shape class (:meth:`compile_stats` —
        trace+lower / compile wall time, jaxpr line count, HLO text
        size), so the stream's compile-time/program-size win is
        inspectable straight off the session; call
        :meth:`compile_stats` directly for a batched or non-f32 class.
        Every scalar reported here is also published to the global
        metrics registry (``repro.obs.registry.REGISTRY``) under
        ``selinv_engine_*`` — the process-wide scrape surface."""
        cls = type(self)
        comm = self.comm_counters()
        out = {"ppermute_rounds": comm["rounds"],
               "wire_bytes": comm["wire_bytes"],
               "recv_bytes_max": comm["recv_bytes_max"],
               "recv_bytes_mean": comm["recv_bytes_mean"],
               "peak_arena_blocks": peak_arena_blocks(
                   self.program.overlap_plan),
               # structure-cache health (class-level, all sessions) +
               # this session's own table footprint — the serving
               # layer's warm-engine dashboard reads these
               "table_bytes": self.table_bytes(),
               "cache_engines": len(cls._cache),
               "cache_hits": cls.cache_hits,
               "cache_misses": cls.cache_misses,
               "cache_evictions": cls.cache_evictions,
               "solve_calls": self.solve_calls,
               "h2d_packed_batches": self.h2d_packed_batches,
               "h2d_dense_batches": self.h2d_dense_batches}
        if self.options.stream:
            from .stream import stream_shifts_per_round, stream_wire_bytes
            st = self.program.stream_tables
            out["stream_wire_bytes"] = stream_wire_bytes(st, self.b)
            out["stream_shifts_per_round"] = stream_shifts_per_round(st)
        if compile:
            # compile metrics require a live trace + XLA compile of the
            # session's sweep when this shape class was never measured
            # (a multi-second side effect, cached afterwards) — and a
            # cached session can outlive the device topology it was
            # analyzed under, so guard with the canonical device check
            # instead of dying deep inside shard_map
            check_grid_devices(self.grid.pr, self.grid.pc)
            out.update(self.compile_stats())
        for k, v in out.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                REGISTRY.gauge(f"selinv_engine_{k}",
                               "engine.stats() gauge").set(v)
        return out
