"""parallel/overlap — T3-style tile-granular compute/comm overlap for
the data-parallel gradient reduction.

The bucketer (parallel/bucketer) fuses gradient leaves into size-capped
buckets; until now a bucket's collective could only start once the WHOLE
bucket was produced. This module tracks readiness at *tile* granularity
inside each bucket (T3, arxiv 2401.16677: track output-tile completion
during backprop, trigger sub-operation collectives as tiles land):

* Each planned bucket becomes ONE persistent
  :class:`ompi_tpu.coll.partitioned.PartitionedAllreduce` —
  Psend_init/Precv_init bound once at session construction, re-armed
  every step by ``start()``. A tile and the partition→transfer
  re-blocking under it therefore can never straddle two buckets: the
  bucketer's fusion boundary IS the partitioned-request boundary.
* :meth:`DpOverlapSession.mark_ready` maps a gradient leaf (or a flat
  slice of one) onto the tiles it covers; fully covered tiles fire as
  coalesced ``Pready_range`` bursts inside one fastpath batch-dispatch
  window, and arrivals drain via ``Parrived`` polling from the progress
  engine — the reduction of early tiles overlaps the backward pass
  still producing late ones.
* The transformer hooks (:func:`grad_marker`,
  :func:`capture_ready_schedule`) record the backprop completion order
  at trace time — custom-VJP identities whose backward rule fires as
  each layer's gradients finish — so host-side training loops (and the
  bench) replay production in true backward order.

Per-step accounting lands in :class:`OverlapReport`:
``dp_step_overlap_pct`` is the fraction of allreduce wall-time hidden
under backprop, ``exposed_comm_ms`` the tail left after backward ends.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import config
from ..core import progress as _progress
from ..core.counters import SPC
from ..core.errors import ArgumentError, RequestError
from ..ops import SUM
from . import bucketer

_tile_bytes_var = config.register(
    "parallel", "overlap", "tile_bytes",
    type=int, default=256 << 10,
    description="Target bytes per readiness tile inside a gradient "
                "bucket (each tile fires one Pready as its gradient "
                "materializes)",
)

SPC.counter(
    "parallel_overlap_marks",
    "mark_ready calls mapped onto bucket tiles",
)


class LeafPiece(NamedTuple):
    """One leaf's flat slice [leaf_lo, leaf_hi) lives in bucket
    ``bucket`` at bucket offsets [bucket_lo, bucket_hi)."""
    bucket: int
    bucket_lo: int
    bucket_hi: int
    leaf_lo: int
    leaf_hi: int


@dataclasses.dataclass
class OverlapPlan:
    """Deterministic leaf→bucket→tile map for one gradient pytree."""
    buckets: list
    leaf_pieces: dict            # leaf_id -> [LeafPiece]
    leaf_paths: list             # leaf_id -> jax keystr
    treedef: Any
    leaf_shapes: list            # per-rank shapes
    leaf_dtypes: list
    # Per-bucket tile geometry. plan_overlap seeds it from its
    # tile_bytes argument; a session compiling a step program stamps
    # the autotuned geometry (winner-cache override included) back
    # here, so the plan always names the geometry that executes.
    tiles: Optional[list] = None
    tile_elems: Optional[list] = None
    tile_sources: Optional[list] = None


def _tile_geometry(elems: int, nbytes: int, tile_bytes: int) -> tuple:
    """(tiles, tile_elems) for one bucket — the same uniform rounding
    PartitionedAllreduce applies."""
    tiles = max(1, min(-(-nbytes // max(1, tile_bytes)), elems))
    te = -(-elems // tiles)
    return -(-elems // te), te


def plan_overlap(per_rank_leaves: list, treedef,
                 bucket_bytes: Optional[int] = None,
                 tile_bytes: Optional[int] = None) -> OverlapPlan:
    """Build the overlap plan over PER-RANK leaves (shapes only). The
    bucket composition is exactly ``bucketer.plan_buckets`` — fusion
    boundaries are shared with the non-overlapped path."""
    plans = bucketer.plan_buckets(per_rank_leaves, bucket_bytes)
    pieces: dict = {}
    for b_idx, bucket in enumerate(plans):
        off = 0
        for leaf_id, lo, hi in bucket.pieces:
            pieces.setdefault(leaf_id, []).append(
                LeafPiece(b_idx, off, off + (hi - lo), lo, hi)
            )
            off += hi - lo
    paths = [f"leaf{i}" for i in range(len(per_rank_leaves))]
    tb = _tile_bytes_var.value if tile_bytes is None else int(tile_bytes)
    geom = [_tile_geometry(b.elems, b.elems * b.dtype.itemsize, tb)
            for b in plans]
    return OverlapPlan(
        buckets=plans,
        leaf_pieces=pieces,
        leaf_paths=paths,
        treedef=treedef,
        leaf_shapes=[tuple(np.shape(l)) for l in per_rank_leaves],
        leaf_dtypes=[jnp.asarray(l).dtype for l in per_rank_leaves],
        tiles=[g[0] for g in geom],
        tile_elems=[g[1] for g in geom],
        tile_sources=["default"] * len(plans),
    )


@dataclasses.dataclass
class OverlapReport:
    """Per-step overlap accounting (the dp_step_overlap_pct source).

    Window sessions (``window >= 2``) additionally account the step's
    merged broadcast tail: ``tail_ms`` is its dispatch wall-time and
    ``tail_overlap_ms`` the share of it hidden under the NEXT step's
    backward pass (the slipstream headline)."""
    backward_ms: float = 0.0
    comm_ms: float = 0.0
    exposed_comm_ms: float = 0.0
    tiles: int = 0
    buckets: int = 0
    tail_ms: float = 0.0
    tail_overlap_ms: float = 0.0

    @property
    def overlap_pct(self) -> float:
        """Fraction (percent) of allreduce wall-time hidden under the
        backward pass."""
        if self.comm_ms <= 0.0:
            return 100.0
        pct = 100.0 * (1.0 - self.exposed_comm_ms / self.comm_ms)
        return max(0.0, min(100.0, pct))


class _TailNode:
    """One closed step's armed broadcast tail, queued for dispatch.

    The claim protocol (claim under the fire lock, run unlocked) lets
    the pump thread dispatch the tail concurrently with the next step's
    backward while flush()/begin_step() can still force-complete it —
    whoever claims first runs ``finish_tail()``; everyone else waits on
    the event."""

    __slots__ = ("exec_", "phase", "report", "event", "claimed",
                 "result", "error")

    def __init__(self, exec_, phase: int, report: OverlapReport) -> None:
        self.exec_ = exec_
        self.phase = phase
        self.report = report
        self.event = threading.Event()
        self.claimed = False
        self.result = None
        self.error: Optional[BaseException] = None


class DpOverlapSession:
    """Host-side tile-granular gradient allreduce session.

    Bind once per (comm, gradient structure); then every step::

        sess.begin_step()
        for name, value in backward_order:   # as grads materialize
            sess.mark_ready(name, value)
        grads, report = sess.finish()

    Leaves are rank-major ``(size, ...)`` buffers (the driver-model
    SPMD view, same convention as ``bucketer.allreduce_pytree``).

    The session's comm is ONE compiled step program
    (:func:`ompi_tpu.coll.sched.stepprogram.compile_step`): the bucket
    list compiles into a multi-collective ``Program`` — per-bucket tile
    geometry from the autotuner's precedence (explicit ``tile_bytes`` >
    winner cache > model), RS/AG-vs-allreduce as a schedule decision
    (pin per bucket via ``node_choices``), cross-bucket interleave —
    and a :class:`~ompi_tpu.coll.sched.stepprogram.StepExecutor` binds
    it to live transport. ``step_program=False`` drops back to the
    PR 15 per-bucket behaviour (one broadcast and one progress
    callback per bucket) — kept as the bench's comparison arm.

    ``window >= 2`` turns the session into a **slipstream window**
    (coll/sched/slipstream): the bucket list compiles through
    :func:`~ompi_tpu.coll.sched.slipstream.compile_window` (shard
    residency included — elided allgathers never build wire flows),
    and the step loop becomes::

        sess.begin_step(); ...mark_ready...; sess.step()   # step N
        sess.begin_step(); ...mark_ready...; sess.step()   # step N+1
        results = sess.flush()       # [(grads, report), ...] in order

    ``step()`` closes the step at ``wait_reduced()`` — reductions done,
    merged broadcast tail ARMED but not drained — and queues the tail
    for the pump thread, which dispatches it concurrently with step
    N+1's backward tile bursts. Each phase of the window owns its own
    executor (disjoint tag ranges), so step N's tail and step N+1's
    reductions coexist on the fabric. ``finish()`` still works (close +
    flush, last step's result) and :meth:`abort_window` collapses the
    window deterministically (the lifeboat path).
    """

    def __init__(self, comm, template: Any, op: Any = SUM,
                 bucket_bytes: Optional[int] = None,
                 tile_bytes: Optional[int] = None,
                 allow_quant: Optional[bool] = None,
                 tag_base: int = 820,
                 progress_thread: bool = True,
                 step_program: bool = True,
                 node_choices: Optional[list] = None,
                 seed: Optional[int] = None,
                 window: int = 1,
                 ag_deadlines: Optional[list] = None) -> None:
        from ..coll.sched.stepprogram import StepExecutor, compile_step

        leaves, treedef = jax.tree.flatten(template)
        if not leaves:
            raise ArgumentError("empty gradient template")
        size = comm.size
        for leaf in leaves:
            shape = np.shape(leaf)
            if len(shape) < 1 or shape[0] != size:
                raise ArgumentError(
                    f"overlap session needs rank-major (size, ...) "
                    f"leaves, got shape {shape}"
                )
        # Full template shapes, kept separately from the plan's PER-RANK
        # shapes: a 1-D (size,) leaf plans as a per-rank (1,) proxy, and
        # reassembly must restore the original (size,) — not (size, 1).
        self._template_shapes = [tuple(np.shape(l)) for l in leaves]
        per_rank = [
            jax.ShapeDtypeStruct(np.shape(l)[1:] or (1,),
                                 jnp.asarray(l).dtype)
            for l in leaves
        ]
        # plan_buckets sizes leaves via jnp.asarray(...).size — feed it
        # zero-cost shape proxies.
        proxies = [np.zeros(s.shape, s.dtype) for s in per_rank]
        self.plan = plan_overlap(proxies, treedef, bucket_bytes)
        paths_leaves = jax.tree_util.tree_flatten_with_path(template)[0]
        self.plan.leaf_paths = [
            jax.tree_util.keystr(p) for p, _ in paths_leaves
        ]
        self._name_to_leaf = {
            p: i for i, p in enumerate(self.plan.leaf_paths)
        }
        self._comm = comm
        self._op = op
        self._window = int(window)
        if self._window < 1:
            raise ArgumentError(f"window must be >= 1, got {window}")
        if self._window >= 2 and not step_program:
            raise ArgumentError(
                "window sessions pipeline compiled step programs — "
                "window >= 2 needs step_program=True")
        # Compile the step: the bucket list becomes one multi-
        # collective Program, and its executor owns every per-bucket
        # flow. Explicit tile_bytes wins; otherwise the autotuner
        # consults the winner cache, then the model — never a static
        # default. Window sessions compile the two-step slipstream
        # window instead (tail node + shard residency + boundary
        # fusion), and execute its repeated step per phase.
        bucket_list = [(b.elems, b.dtype) for b in self.plan.buckets]
        if self._window >= 2:
            from ..coll.sched import slipstream
            self.compiled_window = slipstream.compile_window(
                size, bucket_list, tile_bytes=tile_bytes, seed=seed,
                node_choices=node_choices, ag_deadlines=ag_deadlines)
            self.compiled = self.compiled_window.step
        else:
            self.compiled_window = None
            self.compiled = compile_step(
                size, bucket_list, tile_bytes=tile_bytes, seed=seed,
                node_choices=node_choices, ag_deadlines=ag_deadlines)
        # One executor per window phase, disjoint tag ranges (a
        # ShardedAllreduce consumes nshards tags, everything else one)
        # plus slack, so step N's armed tail and step N+1's reductions
        # coexist on the fabric without tag collisions.
        self._execs = []
        tag = tag_base
        for _ in range(self._window):
            ex = StepExecutor(
                comm, self.compiled, op=op, allow_quant=allow_quant,
                tag_base=tag, legacy=not step_program)
            self._execs.append(ex)
            tag += sum(getattr(b, "nshards", 1)
                       for b in ex.bindings) + 8
        self._phase = 0
        self._pas = self._exec.bindings
        # Stamp the compiled geometry back into the plan so the plan
        # names what executes (the winner-cache override regression
        # hook).
        self.plan.tiles = [n.tiles for n in self.compiled.nodes]
        self.plan.tile_elems = [n.tile_elems for n in self.compiled.nodes]
        self.plan.tile_sources = [n.tile_source
                                  for n in self.compiled.nodes]
        self._stage = [np.zeros((size, b.elems), b.dtype)
                       for b in self.plan.buckets]
        self._covered = None
        self._fired = None
        self._active = False
        self._report = None
        # Async progress pumper (opal progress-thread analog): drains
        # tile arrivals while BOTH the backward producer and the apply
        # consumer are busy in compute — without it, overlap only
        # happens while some caller is blocked inside the engine.
        self._use_pump_thread = bool(progress_thread)
        self._pump_stop: Optional[threading.Event] = None
        self._pump_thread: Optional[threading.Thread] = None
        # Completed tile runs queued for dispatch off the producer
        # thread: mark_ready() stays a staging memcpy plus bookkeeping,
        # the pump thread pays for wire encode + Pready bursts.
        self._fire_q: deque = deque()
        self._fire_lock = threading.Lock()
        # Window state: closed steps whose broadcast tails are armed
        # but not yet drained. _tails keeps step order (flush returns
        # results in it); _tail_q feeds the pump thread's drain pass.
        self._tails: list = []
        self._tail_q: deque = deque()

    @property
    def _exec(self):
        """The executor owning the CURRENT phase of the window (the
        only executor, for window == 1)."""
        return self._execs[self._phase]

    # -- step lifecycle ---------------------------------------------------

    def begin_step(self) -> "DpOverlapSession":
        """Re-arm the compiled step program (every node flow, one
        dispatch window, compiled interleave order) and reset tile
        coverage."""
        if self._active:
            raise RequestError("begin_step() inside an open step")
        if self._window >= 2:
            # A phase's executor cannot re-arm (start() resets the
            # deferred root-local buffers) until its previous tail
            # consumed them — force-complete same-phase pending tails,
            # and surface any tail error the pump thread stashed.
            for rec in self._tails:
                if rec.phase == self._phase and not rec.event.is_set():
                    self._complete_tail(rec)
            for rec in self._tails:
                if rec.error is not None:
                    err = rec.error
                    self.abort_window()
                    raise err
        self._pas = self._exec.bindings
        self._exec.begin_step()
        self._covered = [
            np.zeros(pa.tiles, np.int64) for pa in self._pas
        ]
        self._covmask = [
            np.zeros(b.elems, bool) for b in self.plan.buckets
        ]
        self._fired = [np.zeros(pa.tiles, bool) for pa in self._pas]
        self._fire_q.clear()
        for buf in self._stage:
            buf.fill(0)
        self._active = True
        self._t0 = time.perf_counter()
        self._t_bwd_end = None
        self._report = None
        # Window mode keeps ONE pump thread alive across the whole
        # window (it drains step N's tail under step N+1's backward);
        # single-step mode still cycles it per step.
        if self._use_pump_thread and self._pump_thread is None:
            self._pump_stop = threading.Event()
            self._pump_thread = threading.Thread(
                target=self._pump_loop, args=(self._pump_stop,),
                name="dp-overlap-progress", daemon=True,
            )
            self._pump_thread.start()
        return self

    def _pump_loop(self, stop: threading.Event) -> None:
        """Background drain: dispatch queued tile runs, then pump the
        progress engine (serialized with every other waiter through the
        engine's pumper lock) until the step's buckets are all reduced
        or finish() signals stop."""
        def _quiet() -> bool:
            return (stop.is_set() or bool(self._fire_q)
                    or bool(self._tail_q)
                    or all(pa.reduced for pa in self._pas))

        while not stop.is_set():
            self._drain_fire_q()
            # Queued window tails dispatch HERE, after (outside) the
            # fire queue's batch-dispatch window: the merged broadcast
            # is a blocking collective, and a live shm fabric buffers
            # posts until window exit — running it inside the coalescing
            # window would deadlock it against its own dispatch.
            self._drain_tails()
            if all(pa.reduced for pa in self._pas):
                stop.wait(0.002)
                continue
            _progress.ENGINE.progress_until(_quiet, timeout=0.02)

    def _drain_fire_q(self) -> bool:
        """Dispatch every queued completed-tile run as Pready bursts in
        one coalescing window. Serialized against concurrent callers
        (pump thread vs finish) by the fire lock."""
        from ..coll.partitioned import _batch_window

        if not self._fire_q:
            return False
        with self._fire_lock:
            if not self._fire_q:
                return False
            with _batch_window():
                while self._fire_q:
                    b, run_lo, run_hi = self._fire_q.popleft()
                    pa = self._pas[b]
                    llo = pa.tile_range(run_lo)[0]
                    lhi = pa.tile_range(run_hi)[1]
                    pa.ready_range(run_lo, run_hi,
                                   self._stage[b][:, llo:lhi])
        return True

    # -- window tails -----------------------------------------------------

    def _drain_tails(self) -> bool:
        """Pump-thread drain pass: dispatch every queued window tail
        (deque.popleft is atomic; _run_tail's claim makes a concurrent
        force-complete a no-op here)."""
        ran = False
        while self._tail_q:
            try:
                rec = self._tail_q.popleft()
            except IndexError:
                break
            self._run_tail(rec)
            ran = True
        return ran

    def _run_tail(self, rec: _TailNode) -> None:
        """Claim-then-run one armed tail: the merged per-root broadcast
        (plus resident-shard assembly) of a closed step. Runs UNLOCKED —
        the broadcast is a blocking collective and must not serialize
        mark_ready's fire queue behind it. Errors are stashed on the
        record (re-raised at the next begin_step/flush), never thrown
        off the pump thread."""
        with self._fire_lock:
            if rec.claimed:
                return
            rec.claimed = True
        t0 = time.perf_counter()
        try:
            rec.result = rec.exec_.finish_tail()
        except BaseException as e:  # commlint: allow(broadexcept)
            # stash-and-signal: the pump thread has no caller to unwind
            # into; begin_step()/flush() re-raise this
            rec.error = e
        tail_ms = (time.perf_counter() - t0) * 1e3
        # The tail overlapped iff the NEXT step's backward was still
        # producing while it ran (step open, bwd-end unmarked).
        overlap_ms = (tail_ms if self._active and self._t_bwd_end is None
                      else 0.0)
        rec.report.tail_ms = tail_ms
        rec.report.tail_overlap_ms = overlap_ms
        SPC.record("sched_tail_overlap_ms", overlap_ms)
        rec.event.set()

    def _complete_tail(self, rec: _TailNode) -> None:
        """Force one tail to completion: run it inline if unclaimed,
        else wait out whoever claimed it (the pump thread, mid-bcast)."""
        self._run_tail(rec)
        rec.event.wait()

    def step(self) -> None:
        """Close the open step WITHOUT draining its broadcast tail —
        the slipstream boundary. Reductions are waited to completion
        (``wait_reduced``), the merged tail stays armed and is queued
        for the pump thread to dispatch under the NEXT step's backward.
        Results come back from :meth:`flush` in step order. Unready
        tiles raise with the step still open (mark the rest and step()
        again); a reduction failure collapses the whole window."""
        if self._window < 2:
            raise RequestError(
                "step() needs a window session (window >= 2) — "
                "single-step sessions use finish()")
        if not self._active:
            raise RequestError("step() before begin_step()")
        self._check_all_fired("step")
        self._t_bwd_end = time.perf_counter()
        try:
            self._drain_fire_q()
            self._exec.wait_reduced()
        except BaseException:  # commlint: allow(broadexcept)
            # cleanup-then-reraise: a mid-window reduction failure
            # (timeout, revoke, lifeboat kill) must not leak armed
            # tails or the pump thread — collapse deterministically
            self.abort_window()
            raise
        t_done = max(pa.t_reduce_done for pa in self._pas)
        t_first = min(pa.t_first_ready for pa in self._pas)
        report = OverlapReport(
            backward_ms=(self._t_bwd_end - self._t0) * 1e3,
            comm_ms=max(0.0, (t_done - t_first) * 1e3),
            exposed_comm_ms=max(0.0, (t_done - self._t_bwd_end) * 1e3),
            tiles=sum(pa.tiles for pa in self._pas),
            buckets=len(self._pas),
        )
        rec = _TailNode(self._exec, self._phase, report)
        self._tails.append(rec)
        self._tail_q.append(rec)
        SPC.record("sched_window_spans_total")
        self._report = report
        self._active = False
        self._phase = (self._phase + 1) % self._window

    def flush(self) -> list:
        """Close the window: auto-close an open step, complete every
        queued tail in step order, stop the pump thread, and return
        ``[(grads, report), ...]`` — one entry per step() since the
        last flush. The session resets to phase 0, ready for the next
        window."""
        if self._window < 2:
            raise RequestError(
                "flush() needs a window session (window >= 2)")
        if self._active:
            self.step()
        try:
            for rec in self._tails:
                self._complete_tail(rec)
                if rec.error is not None:
                    raise rec.error
        except BaseException:  # commlint: allow(broadexcept)
            self.abort_window()
            raise
        self._stop_pump()
        out = []
        for rec in self._tails:
            reduced = [np.asarray(r) for r in rec.result]
            out.append((self._reassemble(reduced), rec.report))
        self._tails = []
        self._tail_q.clear()
        self._phase = 0
        return out

    def mark_ready(self, param, value, slice: Optional[tuple] = None
                   ) -> list:
        """Mark a gradient (or a flat slice of one) materialized.

        ``param`` is a leaf index or a leaf path (jax keystr of the
        template tree); ``value`` is the rank-major ``(size, ...)``
        gradient payload for that leaf (or for ``slice=(lo, hi)``, its
        flat element range). Returns the (bucket, tile) pairs this call
        completed — their Pready bursts dispatch coalesced into one
        batch-dispatch window: inline when the session runs without a
        progress thread, otherwise handed to the pump thread so the
        producer pays only the staging copy."""
        from ..coll.partitioned import _batch_window

        if not self._active:
            raise RequestError("mark_ready() before begin_step()")
        leaf_id = self._resolve(param)
        size = self._comm.size
        host = np.asarray(value).reshape(size, -1)
        lo, hi = (0, host.shape[1]) if slice is None else slice
        leaf_elems = int(
            np.prod(self.plan.leaf_shapes[leaf_id], dtype=np.int64)
        ) if self.plan.leaf_shapes[leaf_id] else 1
        if not 0 <= lo < hi <= max(leaf_elems, 1):
            raise ArgumentError(
                f"mark_ready slice [{lo}, {hi}) outside leaf "
                f"{self.plan.leaf_paths[leaf_id]} ({leaf_elems} elems)"
            )
        if host.shape[1] != hi - lo:
            raise ArgumentError(
                f"mark_ready payload has {host.shape[1]} elems per "
                f"rank, slice [{lo}, {hi}) needs {hi - lo}"
            )
        SPC.record("parallel_overlap_marks")
        # Atomic duplicate/overlap validation (the Pready_burst
        # contract): a mark touching any element already marked ready
        # this step raises BEFORE anything from this call is staged or
        # flagged, so an erroneous overlapping mark can never
        # double-count tile coverage or rewrite a fired tile's slab.
        hits = []
        for piece in self.plan.leaf_pieces.get(leaf_id, ()):
            plo = max(piece.leaf_lo, lo)
            phi = min(piece.leaf_hi, hi)
            if phi <= plo:
                continue
            b = piece.bucket
            blo = piece.bucket_lo + (plo - piece.leaf_lo)
            if self._covmask[b][blo: blo + (phi - plo)].any():
                raise RequestError(
                    f"mark_ready [{lo}, {hi}) of leaf "
                    f"{self.plan.leaf_paths[leaf_id]} overlaps elements "
                    "already marked ready this step"
                )
            hits.append((plo, phi, b, blo))
        completed: list = []
        touched: set = set()
        for plo, phi, b, blo in hits:
            self._covmask[b][blo: blo + (phi - plo)] = True
            self._stage[b][:, blo: blo + (phi - plo)] = (
                host[:, plo - lo: phi - lo]
            )
            pa = self._pas[b]
            t_lo = blo // pa.tile_elems
            t_hi = (blo + (phi - plo) - 1) // pa.tile_elems
            for t in range(t_lo, t_hi + 1):
                tlo, thi = pa.tile_range(t)
                self._covered[b][t] += (
                    min(thi, blo + (phi - plo)) - max(tlo, blo)
                )
                touched.add((b, t))
        # Fire every tile this call completed, as contiguous
        # Pready_range bursts in ONE coalescing window. With the pump
        # thread running the runs are queued instead — the staging slab
        # region of a completed tile is never rewritten, so the deferred
        # dispatch reads exactly what was staged here.
        runs: list = []
        for b in sorted({bt[0] for bt in touched}):
            pa = self._pas[b]
            ready = sorted(
                t for (bb, t) in touched if bb == b
                and not self._fired[b][t]
                and self._covered[b][t] == pa.tile_range(t)[1]
                - pa.tile_range(t)[0]
            )
            for run_lo, run_hi in _runs(ready):
                runs.append((b, run_lo, run_hi))
                for t in range(run_lo, run_hi + 1):
                    self._fired[b][t] = True
                    completed.append((b, t))
        if self._pump_thread is not None:
            self._fire_q.extend(runs)
        elif runs:
            with _batch_window():
                for b, run_lo, run_hi in runs:
                    pa = self._pas[b]
                    llo = pa.tile_range(run_lo)[0]
                    lhi = pa.tile_range(run_hi)[1]
                    pa.ready_range(run_lo, run_hi,
                                   self._stage[b][:, llo:lhi])
        return completed

    def poll(self) -> list:
        """Drive one progress round; return the bucket indices whose
        reduction (combine + bcast) has completed so far. A consumer
        thread can start applying those buckets while later buckets are
        still reducing under the backward pass."""
        if not self._active:
            if all(pa.reduced for pa in self._pas):
                # finish() already drained the step under this poller
                return list(range(len(self._pas)))
            raise RequestError("poll() before begin_step()")
        done = []
        passive = self._pump_thread is not None
        for b, pa in enumerate(self._pas):
            # With the pump thread driving progress, read the flag only:
            # an active sweep here would just contend on the pumper lock.
            if pa.reduced or (not passive and pa.poll()):
                done.append(b)
        return done

    def finish(self) -> tuple:
        """Backward pass over: wait out the tail, reassemble the reduced
        pytree, and report the step's overlap accounting.

        Unready tiles raise WITHOUT tearing anything down — the step
        stays open, so the caller can mark the missing leaves and call
        finish() again (or :meth:`abort_step` to give up). A reduction
        failure (e.g. a bucket's wait timeout) tears the step down.

        On a window session this is close-plus-flush: the open step
        closes, every pending tail drains, and the LAST step's
        ``(grads, report)`` is returned (earlier steps' results are
        discarded — call :meth:`step`/:meth:`flush` to keep them)."""
        if self._window >= 2:
            if not self._active and not self._tails:
                raise RequestError("finish() before begin_step()")
            return self.flush()[-1]
        if not self._active:
            raise RequestError("finish() before begin_step()")
        self._check_all_fired("finish")
        self._t_bwd_end = time.perf_counter()
        try:
            self._drain_fire_q()
            reduced = [np.asarray(r) for r in self._exec.wait_all()]
        except BaseException:  # commlint: allow(broadexcept)
            # cleanup-then-reraise: ANY reduction failure (timeout,
            # revoke, interrupt) must not leak the pump thread or the
            # buckets' progress callbacks
            self.abort_step()
            raise
        self._stop_pump()
        self._active = False
        t_done = max(pa.t_reduce_done for pa in self._pas)
        t_first = min(pa.t_first_ready for pa in self._pas)
        self._report = OverlapReport(
            backward_ms=(self._t_bwd_end - self._t0) * 1e3,
            comm_ms=max(0.0, (t_done - t_first) * 1e3),
            exposed_comm_ms=max(0.0, (t_done - self._t_bwd_end) * 1e3),
            tiles=sum(pa.tiles for pa in self._pas),
            buckets=len(self._pas),
        )
        return self._reassemble(reduced), self._report

    def _check_all_fired(self, verb: str) -> None:
        unfired = [
            (b, t) for b, fired in enumerate(self._fired)
            for t in range(len(fired)) if not fired[t]
        ]
        if unfired:
            raise RequestError(
                f"{verb}() with unready tiles {unfired[:8]} — every "
                "gradient leaf must be mark_ready()'d (the step stays "
                f"open: mark the rest and {verb}() again, or "
                "abort_step())"
            )

    def abort_step(self) -> None:
        """Tear down an open step without completing it: stop the pump
        thread, abort every bucket's partitioned pair (dropping their
        progress callbacks), and close the step so the session is not
        left with a leaked callback or a live thread. In-flight wire
        state is abandoned (DESIGN.md §20); re-arming this session is
        only safe once the fabric has drained. No-op between steps.

        On a window session the window is ONE unit of teardown —
        delegates to :meth:`abort_window`."""
        if self._window >= 2:
            self.abort_window()
            return
        if not self._active:
            return
        self._stop_pump()
        self._exec.abort()
        self._active = False

    def abort_window(self) -> None:
        """Deterministically collapse the whole window: stop the pump
        thread FIRST (so no tail is mid-dispatch), abort every phase's
        executor (armed tails included — their deferred locals are
        abandoned with the rest of the in-flight wire state, DESIGN.md
        §20/§22), drop all queued tails and reset to phase 0. Same-seed
        controllers collapsing at the same step recompile the identical
        window afterwards — this is the lifeboat path. No-op when the
        window is idle."""
        if (not self._active and not self._tails
                and self._pump_thread is None):
            return
        self._stop_pump()
        for ex in self._execs:
            ex.abort()
        self._tails = []
        self._tail_q.clear()
        self._active = False
        self._phase = 0

    def _stop_pump(self) -> None:
        if self._pump_thread is not None:
            self._pump_stop.set()
            self._pump_thread.join()
            self._pump_thread = None
            self._pump_stop = None

    def last_report(self) -> Optional[OverlapReport]:
        return self._report

    # -- helpers ----------------------------------------------------------

    def _resolve(self, param) -> int:
        if isinstance(param, int):
            if not 0 <= param < len(self.plan.leaf_paths):
                raise ArgumentError(f"leaf index {param} out of range")
            return param
        leaf_id = self._name_to_leaf.get(param)
        if leaf_id is None:
            matches = [
                i for i, p in enumerate(self.plan.leaf_paths)
                if str(param) in p
            ]
            if len(matches) != 1:
                raise ArgumentError(
                    f"cannot resolve {param!r} to one gradient leaf "
                    f"(matches: {len(matches)})"
                )
            leaf_id = matches[0]
        return leaf_id

    def _reassemble(self, reduced: list):
        size = self._comm.size
        out_leaves = []
        for i, shape in enumerate(self.plan.leaf_shapes):
            elems = int(np.prod(shape, dtype=np.int64)) if shape else 1
            flat = np.zeros((size, elems), self.plan.leaf_dtypes[i])
            for piece in self.plan.leaf_pieces.get(i, ()):
                flat[:, piece.leaf_lo: piece.leaf_hi] = (
                    reduced[piece.bucket][:, piece.bucket_lo:
                                          piece.bucket_hi]
                )
            out_leaves.append(
                jnp.asarray(flat.reshape(self._template_shapes[i]))
            )
        return jax.tree.unflatten(self.plan.treedef, out_leaves)


def _runs(idx: list) -> list:
    """Collapse a sorted index list into inclusive (lo, hi) runs."""
    runs: list = []
    for t in idx:
        if runs and t == runs[-1][1] + 1:
            runs[-1] = (runs[-1][0], t)
        else:
            runs.append((t, t))
    return runs


# ---------------------------------------------------------------------------
# Traced-side readiness capture (custom-VJP hooks)
# ---------------------------------------------------------------------------

#: Backprop completion order captured at trace time: grad_marker's
#: backward rule appends as each marked boundary's cotangent is formed.
_BWD_ORDER: list = []
_LAST_SCHEDULE: Optional[dict] = None


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def grad_marker(x, name: str = ""):
    """Identity whose BACKWARD rule records ``name`` — placed on a
    layer's input, it fires after every gradient inside that layer has
    been produced, capturing the true backprop tile order for the
    overlap session to replay. Forward value and cotangent pass through
    bit-identical."""
    return x


def _grad_marker_fwd(x, name):
    return x, None


def _grad_marker_bwd(name, _res, g):
    note_backward(name)
    return (g,)


grad_marker.defvjp(_grad_marker_fwd, _grad_marker_bwd)


def note_backward(name: str) -> None:
    """Record one backprop completion boundary (trace-time)."""
    _BWD_ORDER.append(name)


def backward_order() -> tuple:
    return tuple(_BWD_ORDER)


def reset_capture() -> None:
    del _BWD_ORDER[:]
    global _LAST_SCHEDULE
    _LAST_SCHEDULE = None


def capture_ready_schedule(tree: Any) -> Any:
    """Trace-time capture of the gradient readiness schedule at the
    sync seam: records the leaf paths about to be reduced together with
    the backprop order the grad markers observed, then returns ``tree``
    unchanged. Host overlap sessions (and the bench) read
    :func:`last_schedule` to replay production in backward order — this
    is the mark_ready/Pready evidence the ``overlapready`` lint rule
    looks for at blocking-reduction call sites."""
    global _LAST_SCHEDULE
    paths_leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    _LAST_SCHEDULE = {
        "leaf_paths": tuple(
            jax.tree_util.keystr(p) for p, _ in paths_leaves
        ),
        "bwd_order": tuple(_BWD_ORDER),
    }
    return tree


def last_schedule() -> Optional[dict]:
    return _LAST_SCHEDULE


# ---------------------------------------------------------------------------
# Readiness order from jax's own program ordering (effects/donation)
# ---------------------------------------------------------------------------

def jaxpr_backward_order(grad_fn, *args) -> tuple:
    """Gradient-leaf production order read off jax's OWN program order:
    trace ``grad_fn`` (a function returning the gradient pytree) to a
    jaxpr and rank each output leaf by the index of the equation that
    produces it. ``eval_jaxpr`` executes equations in exactly this
    order — it is the schedule jax's donation/effects machinery
    sequences against — so leaf i ranking before leaf j means leaf i's
    gradient materializes first in the compiled backward.

    Returns leaf indices (into the flattened gradient pytree) in
    production order.
    """
    closed = jax.make_jaxpr(grad_fn)(*args)
    jaxpr = closed.jaxpr
    pos: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            pos[v] = i
    ranks = []
    for leaf_idx, v in enumerate(jaxpr.outvars):
        # constants / passed-through inputs rank first (produced
        # before any equation runs); Literal outputs have no var
        ranks.append((pos.get(v, -1), leaf_idx))
    return tuple(i for _, i in sorted(ranks))


def readiness_order(grad_fn=None, args: tuple = ()) -> tuple:
    """The overlap session's readiness source: ``("jaxpr", order)``
    from jax's real program ordering when ``grad_fn`` traces, else
    ``("marker", backward_order())`` — the custom-VJP
    :func:`grad_marker` capture. Both name the same thing: the sequence
    gradients materialize in during the backward pass."""
    if grad_fn is not None:
        try:
            return ("jaxpr", jaxpr_backward_order(grad_fn, *args))
        except Exception:  # commlint: allow(broadexcept)
            pass  # fall back to the marker capture
    return ("marker", backward_order())
