"""AsyncServingEngine — double-buffered dispatch + continuous batching.

The synchronous ``ServingEngine.serve`` runs each request group as
group → pad → dispatch → BLOCK: the host sits idle while the device
executes, and every request pays its own padding and dispatch overhead.
This module overlaps those phases (DESIGN.md §8):

  * ``submit(inr_id, coords)`` returns a ticket immediately; rows are
    appended to a per-signature admission queue, NOT dispatched.
  * An admission pump coalesces pending rows into FULL serving chunks
    (``config.chunk_blocks * block`` rows) and dispatches them through the
    artifact's jitted chunk step (``apply_chunk``) the moment a chunk
    fills.  JAX dispatch is asynchronous, so while the device executes
    chunk *i* the host is already grouping and padding chunk *i+1* —
    double buffering with a bounded in-flight queue (``inflight``, default
    two-deep: one executing, one queued).  When the queue is full the
    oldest item is retired first (blocking retrieval); between dispatches
    ready items are retired opportunistically via ``jax.Array.is_ready``
    (non-blocking).  Retirement only WAITS on device results — the
    host-side unpad/scatter of a retired chunk is deferred until right
    after the NEXT dispatch launches, so that host work overlaps the new
    chunk's device execution (the ``serve.unpad`` span shows it).
  * ``drain()`` flushes the remainders (full blocks through the jitted
    block step, one final padded block), retires everything in flight, and
    returns results for every outstanding ticket IN SUBMISSION ORDER.

Continuous batching.  Admission happens at CHUNK BOUNDARIES: a chunk's
rows may span several tickets (requests coalesce — the win over
serve-on-arrival), and for a signature served by several INRs the pump
builds multi-INR chunks whose K lanes are exactly the INRs with pending
rows at that boundary.  A request that arrives mid-stream joins the lane
set at the next chunk (admission); a lane whose rows are exhausted leaves
it (eviction).  Lanes shorter than the chunk are padded with their own
edge row — padding never reaches a caller.

Parity.  Every op in the block pipeline is row-wise (a query row's outputs
depend only on that row and the weights), and async dispatch reuses the
same jitted chunk/block steps at the same shapes, so repacking rows across
chunk boundaries returns BIT-IDENTICAL results to the synchronous path —
asserted by tests/test_async_serve.py and the serving benchmark.

Routing matches the sync engine at each dispatch: a signature whose only
pending lane is the base weight set takes the single-INR fast path;
anything else takes the multi-INR (stacked-resident) path.
"""

from __future__ import annotations

import math
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.obs.metrics import counter as _obs_counter, histogram
from repro.obs.tracing import TRACER
from repro.serve.engine import ServingEngine
from repro.serve.multi_inr import pad_rows

# async-only stats keys layered onto the inherited engine view
_ASYNC_METRICS = {
    "submitted": ("serve_submitted", "requests submitted (async)"),
    "async_chunks": ("serve_async_chunks",
                     "full single-INR chunks dispatched"),
    "async_blocks": ("serve_async_blocks", "remainder blocks dispatched"),
    "async_multi_chunks": ("serve_async_multi_chunks",
                           "multi-INR chunks dispatched"),
    "admissions": ("serve_admissions",
                   "lane admissions at chunk boundaries"),
    "evictions": ("serve_evictions", "lane evictions at chunk boundaries"),
    "max_inflight": ("serve_max_inflight", "peak dispatch queue depth"),
}

# per-request latency histograms (DESIGN.md §10): queue-wait is the time a
# dispatched item sat in flight before retirement began; request latency is
# submit (admission) to the scatter of the request's final row
_LAT_QUEUE = histogram("serve_queue_wait_latency_s",
                       "per-item dispatch-to-retire queue wait")
_LAT_REQ = histogram("serve_request_latency_s",
                     "per-request submit-to-retire latency")


def _is_ready(x) -> bool:
    try:
        return bool(x.is_ready())
    except AttributeError:      # non-jax leaf (plain numpy): always ready
        return True


@dataclass
class _Ticket:
    """One submitted request: assembly state for its results."""
    inr_id: str
    sig: str
    wid: str
    n: int                                   # rows requested
    filled: int = 0                          # rows scattered so far
    t_submit: float = 0.0                    # admission time (latency histo)
    bank_j: int = -1                         # bank output index (-1: not bank)
    # streamed-output position -> [(row offset in ticket, slice), ...]
    parts: dict = field(default_factory=dict)

    def scatter(self, o_idx: int, tstart: int, val) -> None:
        self.parts.setdefault(o_idx, []).append((tstart, val))


@dataclass
class _Pending:
    """A lane of not-yet-dispatched rows for one INR: a FIFO of ticket
    slices ``(ticket_idx, coords, tstart)``, rows ``coords[tstart:]``
    still pending.  Rows leave through a slice at a DYNAMIC offset, so a
    request split across many chunks compiles one slice program per
    (request shape, take size), never one per remainder shape (each such
    compile took about a quarter of a second on a TPU v5e)."""
    slices: deque = field(default_factory=deque)
    rows: int = 0
    feat_shape: tuple = ()
    dtype: object = None

    def push(self, ticket_idx: int, coords) -> None:
        self.slices.append((ticket_idx, coords, 0))
        self.rows += int(coords.shape[0])
        self.feat_shape = tuple(coords.shape[1:])
        self.dtype = coords.dtype

    def take(self, n: int):
        """Pop up to ``n`` rows; returns (coords [m, ...], scatter) where
        scatter is [(ticket_idx, tstart, start-in-coords, count), ...].
        A drained lane yields 0 rows (an exhausted generation lane rides
        along as padding)."""
        cols, scatter, got = [], [], 0
        while got < n and self.slices:
            ti, c, tstart = self.slices.popleft()
            m = int(c.shape[0]) - tstart
            take = min(m, n - got)
            cols.append(c if take == int(c.shape[0])
                        else jax.lax.dynamic_slice_in_dim(c, tstart, take))
            scatter.append((ti, tstart, got, take))
            got += take
            if take < m:
                self.slices.appendleft((ti, c, tstart + take))
        self.rows -= got
        if not cols:
            return jnp.zeros((0,) + self.feat_shape, self.dtype), scatter
        coords = cols[0] if len(cols) == 1 else jnp.concatenate(cols)
        return coords, scatter


@dataclass
class _InFlight:
    """A dispatched (not yet retired) device computation."""
    kind: str                  # "chunk" | "block" | "multi"
    outs: tuple                # streamed outputs, still materializing
    scatter: list              # entries, shape depends on kind
    t_dispatch: float
    rows: int


class AsyncServingEngine(ServingEngine):
    """ServingEngine with asynchronous, continuously-batched dispatch.

    ``inflight`` bounds the dispatch queue depth (2 = double buffering).
    ``serve`` (inherited) stays available as the synchronous baseline;
    ``serve_async`` is its overlapped equivalent and returns bit-identical
    results in the same request order.
    """

    def __init__(self, store=None, *, inflight: int = 2, **kw):
        super().__init__(store, **kw)
        if inflight < 1:
            raise ValueError(f"inflight must be >= 1, got {inflight}")
        self.inflight = int(inflight)
        self._tickets: list[_Ticket] = []
        self._retired: deque[_InFlight] = deque()   # awaiting host unpad
        self._drained_upto = 0
        # sig -> OrderedDict[inr_id -> _Pending]  (admission queues)
        self._pending: "OrderedDict[str, OrderedDict[str, _Pending]]" = \
            OrderedDict()
        # bank sig -> _Pending: ONE lane per bank — filter requests of one
        # bank share the merged graph, so their rows coalesce into a single
        # concatenated pass per admission boundary (sync-path grouping)
        self._bank_pending: "OrderedDict[str, _Pending]" = OrderedDict()
        # sig -> lane tuple fixed at the last admission boundary (see _pump)
        self._gen: dict[str, tuple[str, ...]] = {}
        self._queue: deque[_InFlight] = deque()
        for k, (name, help) in _ASYNC_METRICS.items():
            self.stats.with_key(k, _obs_counter(name, help))
        self.stats.reset()       # async keys start at zero on this label

    # -- submission --------------------------------------------------------

    def _enqueue(self, inr_id: str, coords) -> int:
        t0 = time.perf_counter()
        if inr_id in self._bank_routes:
            return self._enqueue_bank(inr_id, coords, t0)
        if inr_id not in self._routes:
            raise KeyError(f"unregistered inr_id {inr_id!r}")
        sig, wid = self._routes[inr_id]
        coords = jnp.asarray(coords)
        ticket = len(self._tickets)
        self._tickets.append(_Ticket(inr_id, sig, wid, int(coords.shape[0]),
                                     t_submit=t0))
        self.stats["submitted"] += 1
        self.stats["requests"] += 1
        if coords.shape[0]:
            lanes = self._pending.setdefault(sig, OrderedDict())
            if inr_id not in lanes:
                lanes[inr_id] = _Pending()
                self.stats["admissions"] += 1
            lanes[inr_id].push(ticket, coords)
        return ticket

    def _enqueue_bank(self, fid: str, coords, t0: float) -> int:
        """Queue a filter-bank request: all filters of one bank share a
        single pending lane — their rows run as ONE concatenated pass of
        the merged graph at the next admission boundary."""
        sig, j = self._bank_routes[fid]
        coords = jnp.asarray(coords)
        ticket = len(self._tickets)
        self._tickets.append(_Ticket(fid, sig, "", int(coords.shape[0]),
                                     t_submit=t0, bank_j=j))
        self.stats["submitted"] += 1
        self.stats["requests"] += 1
        if coords.shape[0]:
            if sig not in self._bank_pending:
                self._bank_pending[sig] = _Pending()
                self.stats["admissions"] += 1
            self._bank_pending[sig].push(ticket, coords)
        return ticket

    def submit(self, inr_id: str, coords) -> int:
        """Enqueue one request; returns its ticket index.  Full chunks
        dispatch immediately (overlapping any execution in flight); partial
        rows wait for coalescing until ``drain``."""
        ticket = self._enqueue(inr_id, coords)
        self._pump(flush=False)
        self._poll()
        return ticket

    def serve_async(self, requests):
        """Asynchronous counterpart of ``serve``: enqueue every request,
        then drain — results in request order, BIT-IDENTICAL to one sync
        ``serve`` call over the same list.  Enqueueing the whole batch
        before the pump runs fixes each signature's lane generation to
        exactly the sync path's grouping (XLA specializes K=1 math, so
        mixing a lone-lane dispatch into a stream the sync path serves
        multi-INR would change low bits); the double-buffered overlap
        happens across the chunks of the drain."""
        tickets = [self._enqueue(i, c) for i, c in requests]
        results = self.drain()
        base = tickets[0] if tickets else 0
        return [results[t - base] for t in tickets]

    def drain(self):
        """Flush all pending rows, retire everything in flight, and return
        the results of every ticket since the last drain, in submission
        order."""
        self._pump(flush=True)
        while self._queue:
            self._retire(self._queue.popleft())
        self._unpad_retired()
        out = [self._finalize(t)
               for t in self._tickets[self._drained_upto:]]
        self._drained_upto = len(self._tickets)
        return out

    def pending_rows(self) -> int:
        return (sum(p.rows for lanes in self._pending.values()
                    for p in lanes.values())
                + sum(p.rows for p in self._bank_pending.values()))

    # -- the admission pump ------------------------------------------------

    def _pump(self, *, flush: bool) -> None:
        """Dispatch every admissible chunk.  Admission/eviction happens at
        chunk boundaries: a newly-submitted lane joins the serving set (the
        GENERATION) at the next boundary, and that reform also drops lanes
        that have drained (eviction).  Between reforms the generation is
        FIXED — an exhausted lane rides along as padding rather than
        shrinking K, so every chunk of a generation hits one compiled trace
        and, crucially, rows keep the exact bit pattern of the sync path
        (XLA specializes K=1 vmapped math, so a shrinking lane count would
        flip low bits mid-stream)."""
        for sig in list(self._pending):
            lanes = self._pending[sig]
            gen = self._gen.get(sig)
            while True:
                live = [i for i, p in lanes.items() if p.rows > 0]
                if not live:
                    # generation fully drained: evict every lane
                    self.stats["evictions"] += len(gen or ())
                    self._gen.pop(sig, None)
                    del self._pending[sig]
                    break
                if gen is None or any(i not in gen for i in live):
                    # admission boundary: new lanes join, drained ones leave
                    if gen is not None:
                        dropped = [i for i in gen if i not in live]
                        self.stats["evictions"] += len(dropped)
                        for i in dropped:
                            lanes.pop(i, None)
                    gen = tuple(i for i in lanes if i in live)
                    self._gen[sig] = gen
                cg = self._artifact(sig)
                block = cg.config.block
                chunk_rows = cg.config.chunk_blocks * block
                single = (len(gen) == 1
                          and self._routes[gen[0]][1]
                          == self._base_wid.get(sig))
                n_max = max(lanes[i].rows for i in gen)
                if single:
                    p = lanes[gen[0]]
                    if p.rows >= chunk_rows:
                        self._dispatch_single_chunk(sig, p, chunk_rows)
                    elif flush:
                        self._flush_single(sig, p)
                    else:
                        break
                else:
                    if n_max >= chunk_rows or flush:
                        nb = min(cg.config.chunk_blocks,
                                 math.ceil(n_max / block))
                        self._dispatch_multi(sig, lanes, gen, nb)
                    else:
                        break
        self._pump_banks(flush=flush)

    def _pump_banks(self, *, flush: bool) -> None:
        """Dispatch bank lanes whose pending rows fill a chunk (or on
        flush): the whole lane goes out as ONE concatenated pass of the
        merged graph — the sync path's per-signature bank grouping, so the
        ``bank_groups`` counter advances identically."""
        for sig in list(self._bank_pending):
            p = self._bank_pending[sig]
            bank = self._bank(sig)
            chunk_rows = bank.cg.config.chunk_blocks * bank.cg.config.block
            if p.rows and (p.rows >= chunk_rows or flush):
                self._dispatch_bank(sig, p)
            if p.rows == 0:
                self.stats["evictions"] += 1
                del self._bank_pending[sig]

    # -- dispatch ----------------------------------------------------------

    def _dispatch(self, item: _InFlight) -> None:
        while len(self._queue) >= self.inflight:
            self._retire(self._queue.popleft())
        self._queue.append(item)
        self.stats["max_inflight"] = max(self.stats["max_inflight"],
                                         len(self._queue))
        # the item just dispatched is executing on-device NOW — scatter any
        # retired results while it runs (host unpad overlaps device exec)
        self._unpad_retired()

    def _dispatch_single_chunk(self, sig: str, p: _Pending,
                               chunk_rows: int) -> None:
        with TRACER.span("serve.chunk", cat="serve", sig=sig[:12],
                         rows=chunk_rows):
            cg = self._artifact(sig)
            block = cg.config.block
            with TRACER.span("serve.pad", cat="serve"):
                coords, scatter = p.take(chunk_rows)
                xc = coords.reshape(chunk_rows // block, block,
                                    *coords.shape[1:])
            self.stats["async_chunks"] += 1
            self.stats["rows"] += chunk_rows
            with TRACER.span("serve.dispatch", cat="serve"):
                outs = cg.apply_chunk(xc)
            self._dispatch(_InFlight("chunk", outs, scatter,
                                     time.perf_counter(), chunk_rows))

    def _flush_single(self, sig: str, p: _Pending) -> None:
        """Drain a partial single-INR lane: full blocks through the jitted
        block step, the final partial block edge-padded (padding rows are
        simply never scattered)."""
        cg = self._artifact(sig)
        block = cg.config.block
        while p.rows:
            with TRACER.span("serve.block", cat="serve", sig=sig[:12]):
                n = min(block, p.rows)
                with TRACER.span("serve.pad", cat="serve"):
                    coords, scatter = p.take(n)
                    if n < block:
                        coords = pad_rows(coords, block)
                self.stats["rows"] += n
                self.stats["padded_rows"] += block - n
                self.stats["async_blocks"] += 1
                with TRACER.span("serve.dispatch", cat="serve"):
                    outs = cg.apply_block(coords)
                self._dispatch(_InFlight("block", outs, scatter,
                                         time.perf_counter(), n))

    def _dispatch_multi(self, sig: str, lanes, active, nb: int) -> None:
        """One continuous-batching round: a [nb, K, block, ...] chunk whose
        K lanes are the INRs admitted at this boundary."""
        with TRACER.span("serve.chunk.multi", cat="serve", sig=sig[:12],
                         lanes=len(active)):
            cg = self._artifact(sig)
            block = cg.config.block
            take = nb * block
            wids = tuple(self._routes[i][1] for i in active)
            m = self._multi_artifact(sig, wids)
            cols, scatter = [], []
            for k, inr_id in enumerate(active):
                p = lanes[inr_id]
                with TRACER.span("serve.pad", cat="serve", tid=k + 1,
                                 lane=inr_id):
                    n = min(p.rows, take)
                    coords, sc = p.take(n)
                    cols.append(pad_rows(coords, take))
                self.stats["rows"] += n
                self.stats["padded_rows"] += take - n
                scatter.extend((ti, tstart, k, start, count)
                               for ti, tstart, start, count in sc)
            batch = jnp.stack(cols)                    # [K, take, ...]
            xb = jnp.moveaxis(
                batch.reshape(len(active), nb, block, *batch.shape[2:]),
                0, 1)
            self.stats["async_multi_chunks"] += 1
            if m.k_sharded:
                self.stats["k_sharded_batches"] += 1
            with TRACER.span("serve.dispatch", cat="serve"):
                outs = m.apply_chunk(xb)
            self._dispatch(_InFlight("multi", outs, scatter,
                                     time.perf_counter(),
                                     take * len(active)))

    def _dispatch_bank(self, sig: str, p: _Pending) -> None:
        """One concatenated bank pass: every pending filter request of the
        bank rides one streamed execution of the merged multi-output graph
        (request k for filter j later reads its row slice of output j)."""
        with TRACER.span("serve.chunk.bank", cat="serve", sig=sig[:12],
                         rows=p.rows):
            bank = self._bank(sig)
            n = p.rows
            with TRACER.span("serve.pad", cat="serve"):
                coords, scatter = p.take(n)
            self.stats["bank_groups"] += 1
            self.stats["rows"] += n
            self.stats["padded_rows"] += (-n) % bank.cg.config.block
            with TRACER.span("serve.dispatch", cat="serve", bank=True):
                outs = bank.apply_batched(self._place(coords, 0))
            self._dispatch(_InFlight("bank", outs, scatter,
                                     time.perf_counter(), n))

    # -- retirement / assembly ---------------------------------------------

    def _poll(self) -> None:
        """Retire ready items without blocking (front of the queue first —
        retiring out of order would not preserve FIFO depth semantics)."""
        while self._queue and all(_is_ready(o) for o in self._queue[0].outs):
            self._retire(self._queue.popleft())

    def _retire(self, item: _InFlight) -> None:
        """Block until the item's device results are ready, then queue it
        for host-side scatter.  The scatter itself (``_unpad_retired``) is
        DEFERRED: ``_dispatch`` runs it right after launching the next
        chunk, so unpadding retired results overlaps that chunk's device
        execution instead of sitting on the critical path."""
        _LAT_QUEUE.observe(time.perf_counter() - item.t_dispatch,
                           engine=self.stats.labels["engine"])
        with TRACER.span("serve.retire", cat="serve", kind=item.kind,
                         rows=item.rows):
            jax.block_until_ready(item.outs)
        self._retired.append(item)

    def _unpad_retired(self) -> None:
        """Scatter every retired item's rows into its tickets (dropping
        padding — it never reaches a caller), inside ``serve.unpad``."""
        if not self._retired:
            return
        with TRACER.span("serve.unpad", cat="serve",
                         items=len(self._retired)):
            while self._retired:
                self._scatter_item(self._retired.popleft())

    def _scatter_item(self, item: _InFlight) -> None:
        if item.kind == "multi":
            # outs: each [nb, K, block, ...] -> per-lane flat rows
            flat = [jnp.moveaxis(o, 0, 1).reshape(
                        o.shape[1], o.shape[0] * o.shape[2], *o.shape[3:])
                    for o in item.outs]
            for ti, tstart, lane, start, count in item.scatter:
                t = self._tickets[ti]
                for o_idx, o in enumerate(flat):
                    t.scatter(o_idx, tstart, o[lane, start:start + count])
                t.filled += count
                self._observe_ticket(t)
        elif item.kind == "bank":
            # outs: one [N, ...] array per bank output, already row-flat;
            # each ticket reads only ITS filter's output
            for ti, tstart, start, count in item.scatter:
                t = self._tickets[ti]
                t.scatter(0, tstart,
                          item.outs[t.bank_j][start:start + count])
                t.filled += count
                self._observe_ticket(t)
        else:
            # "chunk": each [nb, block, ...] -> flat rows; "block": already
            # [block, ...]
            flat = [o.reshape(o.shape[0] * o.shape[1], *o.shape[2:])
                    if item.kind == "chunk" else o
                    for o in item.outs]
            for ti, tstart, start, count in item.scatter:
                t = self._tickets[ti]
                for o_idx, o in enumerate(flat):
                    t.scatter(o_idx, tstart, o[start:start + count])
                t.filled += count
                self._observe_ticket(t)

    def _observe_ticket(self, t: _Ticket) -> None:
        """Record submit-to-last-row latency once a ticket fills."""
        if t.n > 0 and t.filled == t.n and t.t_submit:
            _LAT_REQ.observe(time.perf_counter() - t.t_submit,
                             engine=self.stats.labels["engine"])

    def _finalize(self, t: _Ticket):
        if t.bank_j >= 0:
            return self._finalize_bank(t)
        cg = self._artifact(t.sig)
        if t.filled != t.n:
            raise RuntimeError(f"ticket for {t.inr_id!r} assembled "
                               f"{t.filled}/{t.n} rows")
        outs = []
        s_idx = 0
        for o in cg.graph.outputs:
            if o in cg.plan.resident:
                outs.append(self._resident_out(t, o))
                continue
            if t.n == 0:
                outs.append(jnp.zeros(
                    (0,) + tuple(cg.graph.nodes[o].shape[1:]),
                    cg.graph.nodes[o].dtype))
            else:
                parts = sorted(t.parts[s_idx], key=lambda p: p[0])
                cols = [v for _, v in parts]
                outs.append(cols[0] if len(cols) == 1
                            else jnp.concatenate(cols))
            s_idx += 1
        return tuple(outs)

    def _finalize_bank(self, t: _Ticket):
        """A bank ticket returns a 1-tuple: its filter's output rows (the
        sync path's ``(outs[j][row:row+n],)`` shape)."""
        if t.filled != t.n:
            raise RuntimeError(f"ticket for {t.inr_id!r} assembled "
                               f"{t.filled}/{t.n} rows")
        if t.n == 0:
            g = self._bank(t.sig).cg.graph
            node = g.nodes[g.outputs[t.bank_j]]
            return (jnp.zeros((0,) + tuple(node.shape[1:]), node.dtype),)
        parts = sorted(t.parts[0], key=lambda p: p[0])
        cols = [v for _, v in parts]
        return (cols[0] if len(cols) == 1 else jnp.concatenate(cols),)

    def _resident_out(self, t: _Ticket, o: int):
        """Resident (const-derived) outputs depend on the weight set, not
        the rows: base weights read the artifact's own residents, any other
        set reads its (cached) K=1 stacked residents — bitwise the same
        values the sync multi path returns."""
        if t.wid == self._base_wid.get(t.sig):
            return self._artifact(t.sig).resident_output(o, t.n)
        m = self._multi_artifact(t.sig, (t.wid,))
        return m.resident_output(o, t.n)[0]

    # -- introspection -----------------------------------------------------

    def describe(self) -> str:
        st = self.stats
        return (super().describe()
                + f"\n  async: inflight<= {self.inflight} "
                f"(peak {st['max_inflight']}), "
                f"{st['async_chunks']} chunks / {st['async_blocks']} blocks "
                f"/ {st['async_multi_chunks']} multi-chunks dispatched, "
                f"{st['admissions']} lane admissions / "
                f"{st['evictions']} evictions, "
                f"{self.pending_rows()} rows pending")
