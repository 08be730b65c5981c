"""Continuous (in-flight) batching: requests join a RUNNING decode.

The window batcher (dl/serve.Batcher) coalesces only requests that arrive
within a few ms of each other; anything landing mid-decode waits for the
whole previous ragged decode. This engine removes that wait: a fixed slot
array decodes forever in ``chunk_size``-step compiled chunks, and new
requests are admitted into free slots at chunk boundaries — iteration-level
scheduling (the vLLM/Orca idea), built the TPU way:

- **Static shapes, compile-once.** One KV cache of ``[max_slots, max_len]``
  per layer lives on device for the engine's lifetime (donated through
  every step, no reallocation). One chunk program serves every mix of
  requests; per-slot prompt lengths, decode depths, and sampling controls
  are traced VECTOR inputs, never shapes. Prefills compile per 16-bucketed
  prompt length, exactly like the stream/batcher paths.
- **Where a slot's KV lives is not the engine's business**
  (dl/kv_layout.py): every program below is written once against a layout
  object, and the engine only reserves and releases through it. Dense, a
  reservation always succeeds. Paged (``page_size`` > 0), the per-layer
  state is a POOL of fixed-size pages plus a host-managed block table, so
  HBM scales with LIVE tokens instead of ``max_slots x max_len`` — slot
  count can grow (32+) without a quadratic HBM bill, admissions reserve
  their span's pages up front (waiting FIFO when the pool is full),
  retirements recycle them.
- **Admission = prefill into a fresh [1, S] cache + one
  dynamic_update_slice of that cache into the slot's rows.** The running
  batch never re-prefills, and the prefill cost is one [S]-length row copy
  per layer on top of the forward itself.
- **Chunked prefill (``prefill_chunk`` > 0, the Sarathi-Serve idea):** a
  long prompt no longer admits as ONE monolithic prefill that stalls
  every active decode row for its whole length. Instead the prompt splits
  into fixed-size pieces (``prefill_chunk`` tokens, 16-bucketed) and the
  scheduler interleaves them with decode chunks at boundaries under a
  per-boundary token budget (``prefill_budget``): decode rows spend
  their ``chunk_size`` tokens first, then prefill pieces pack into the
  remainder (the head piece always lands so fills can't starve). A
  filling row occupies its slot but emits nothing; each piece runs
  against the slot's own cache rows at the row's running offset and the
  LAST piece samples the row's first token from its final-position
  logits (step 0 of the row's (seed, step) stream — token-exact vs the
  single-program admission). Short prompts (<= one piece) keep the
  single-program fast path; prefix-cache hits seed the filling row's
  offset so only the suffix is chunk-prefilled; in paged mode a filling
  row reserves its pages INCREMENTALLY per piece (not the whole span up
  front), so long prompts stop serializing behind the pool-full FIFO —
  a fill that cannot get its next piece's pages simply waits a boundary,
  and if every fill is page-blocked with no decode rows left to retire,
  the youngest fill is preempted back to the arrival queue (it has
  emitted nothing, so the restart is exact).
- **Idle slots decode garbage harmlessly** (same trick as the ragged
  batcher's pad rows): attention per row sees only that row's cache, so an
  idle row's tokens are discarded on the host and its cache rows are
  overwritten wholesale at the next admission.

- **Pipelined dispatch (ISSUE 7):** the chunk boundary is built so the
  host's job per boundary is ASYNCHRONOUS. Three pieces compose: (1)
  dispatch-ahead — the decode carry (cache, tok, offsets) lives on
  device, so the loop keeps up to ``pipeline_depth`` chunk programs in
  flight and starts each result's device→host copy at dispatch time
  (``copy_to_host_async``); the oldest chunk's tokens are fetched one
  boundary LATE, while a younger chunk runs, so EOS/stop/cancel/deadline
  detection lags bounded in-flight work but token values never change.
  (2) multi-chunk decode programs — ``dispatch_depth`` (0 = auto): when
  every slot is in steady decode (no admission, fill piece, or flush
  due), one program scans D x ``chunk_size`` steps, amortizing the fixed
  per-dispatch cost D-fold; depth snaps back to 1 the moment any
  boundary event is pending, and D is capped so no row's writes pass its
  validated ``_overrun`` span. (3) boundary-prep overlap — while chunks
  execute, the loop drains the submit queue and pre-computes the
  expensive admission prep (poison fingerprint, prefix-cache lookup) for
  the backlog head, so an admission boundary is "swap prepared inputs +
  dispatch", not serial host work. Per-boundary host time (minus the
  token-fetch wait) feeds a histogram surfaced as
  ``boundary_host_ms_p50/p99`` in ``snapshot()``.

Token-exactness: a request decoded here yields EXACTLY the tokens the same
request gets from the plain paths — greedy rows by argmax determinism, and
sampled rows because the per-row (seed, step) stream (ops/sampling.py)
depends only on the row's own request seed and decode depth, both carried
per slot — which also makes token sequences DISPATCH-SCHEDULE-INVARIANT:
depth-D programs and deep pipelines replay the identical (seed, step)
sequence, so pipelined output is byte-equal to serial output. Tests assert
byte-equality against ragged_greedy_generate and across dispatch depths.

No reference equivalent (the reference stores models; it cannot serve
them); this is the serving half of the BASELINE north star. Bench target:
8 concurrent clients sustain >= 0.8x the batch-8 decode throughput.
"""

from __future__ import annotations

import functools
import logging
import queue
import threading
import time
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from modelx_tpu.dl import aot_cache, kv_layout
from modelx_tpu.dl.serving_errors import (
    DeadlineExceededError,
    EngineBrokenError,
    PoisonedRequestError,
    QueueFullError,
    ServingError,
)
from modelx_tpu.models.decode import SEQ_BUCKET, pad_seq_len
from modelx_tpu.testing import faults as _faults
from modelx_tpu.utils import devmem, flightrec, promexp, trace, tswheel

_DONE = object()  # end-of-stream sentinel on per-request output queues
_NO_HIT = object()  # "no memoized prefix-cache lookup" sentinel (None = a miss)


# The engine thread's time, tiled (utils/trace.Phases): every instant of
# ``_loop`` is in exactly one of these. ``firsts_wait`` and ``wait_tokens``
# block on the device (what ``_sync_wait_s`` adds up), ``idle`` blocks on an
# empty queue; the rest is host work, and between two chunk dispatches it
# is what ``boundary_host_ms`` measures as one number.
_PHASES = ("sweep", "idle", "admit_prep", "admit_dispatch", "chunk_dispatch",
           "pieces", "firsts_wait", "wait_tokens", "fanout", "overlap_prep",
           "spec")
(_P_SWEEP, _P_IDLE, _P_ADMIT_PREP, _P_ADMIT_DISPATCH, _P_CHUNK_DISPATCH,
 _P_PIECES, _P_FIRSTS_WAIT, _P_WAIT_TOKENS, _P_FANOUT, _P_OVERLAP_PREP,
 _P_SPEC) = range(len(_PHASES))


def _fingerprint(ids, n: int) -> tuple:
    """Identity of one request for poison quarantine: cheap, deterministic,
    and content-addressed (two submissions of the same prompt+budget hash
    alike whatever objects carried them)."""
    import zlib

    return (int(zlib.crc32(np.asarray(ids, np.int32).tobytes())), len(ids), int(n))


class _Ticket:
    """One submitted request: its output queue + a cancellation flag.
    ``cancel()`` (idempotent, any thread) tells the engine the consumer is
    gone — the row's slot frees at the next chunk boundary instead of
    decoding to its full budget into a queue nobody drains.
    ``deadline`` (monotonic seconds, None = none) is set at submit from the
    engine's --request-timeout CLAMPED by any per-request budget the
    transport propagated (the router's ``X-ModelX-Deadline-Ms``): the loop
    expires the request at the next chunk boundary once passed, whatever
    state it is in; ``timeout_s`` records the effective budget so the 504
    names the number that actually applied."""

    __slots__ = ("out", "cancelled", "deadline", "timeout_s", "restart",
                 "request_id", "t_submit", "t_admit", "t_first",
                 "prefill_pieces", "preempts", "resume_step")

    def __init__(self) -> None:
        self.out: "queue.Queue" = queue.Queue()
        self.cancelled = False
        self.deadline: float | None = None
        self.timeout_s: float = 0.0
        # set when a preempted fill re-enters the backlog: its exact
        # restart goes ahead of newer arrivals (re-grab livelock guard),
        # so priority-aware inserts must never cut in front of it
        self.restart = False
        # per-request phase timeline (ISSUE 13): monotonic stamps written
        # by the one thread that owns each transition — submit() on the
        # caller's thread, slot claim + first-token delivery on the engine
        # thread — so no stamp needs a lock. t_admit/t_first stick at
        # their FIRST write: a preempted fill's restart re-claims a slot
        # but the request queued only once.
        self.request_id = ""
        self.t_submit = 0.0
        self.t_admit = 0.0
        self.t_first = 0.0
        self.prefill_pieces = 0
        self.preempts = 0
        self.resume_step = 0

    def cancel(self) -> None:
        self.cancelled = True

    def timing(self) -> dict:
        """The phase breakdown this ticket observed (ms, monotonic-clock
        deltas); phases that never happened (no slot claimed, no first
        token) are simply absent, so a shed/expired request still reports
        what it DID spend."""
        t: dict = {}
        if self.t_submit and self.t_admit:
            t["queue_ms"] = round((self.t_admit - self.t_submit) * 1e3, 3)
        if self.t_admit and self.t_first:
            t["prefill_ms"] = round((self.t_first - self.t_admit) * 1e3, 3)
        if self.t_submit and self.t_first:
            t["ttft_ms"] = round((self.t_first - self.t_submit) * 1e3, 3)
        if self.prefill_pieces:
            t["prefill_pieces"] = self.prefill_pieces
        if self.preempts:
            t["preempts"] = self.preempts
        if self.resume_step:
            t["resume_step"] = self.resume_step
        return t


class _Row:
    """One admitted request row bound to a slot."""

    __slots__ = ("slot", "budget", "emitted", "ticket", "skip", "stops",
                 "closed", "seq", "greedy", "ngram", "ng_len", "tok_pending")

    def __init__(self, slot: int, budget: int, ticket: _Ticket,
                 stops: frozenset = frozenset(), seq: list | None = None,
                 greedy: bool = True) -> None:
        self.slot = slot
        self.budget = budget
        self.emitted = 0
        self.ticket = ticket
        # the chunk scan emits each step's ENTRY carry token, so a freshly
        # admitted row's first chunk re-emits the prefill token the
        # admission already delivered — skip it once
        self.skip = 1
        self.stops = stops  # stop token ids; hit = end the row early
        # set by delivery on a stop hit (value-dependent, so it lags the
        # value-independent plan by <= 1 chunk); plan retires closed rows
        self.closed = False
        # speculation bookkeeping (engine speculative_k > 0): the row's
        # full token history + a lazily built n-gram index over it
        self.seq = seq
        self.greedy = greedy
        self.ngram = None
        self.ng_len = 0
        # True when the engine's tok vector holds this row's NEXT token,
        # computed by a chunk but not yet delivered (chunks emit entry
        # carries, so the freshest token always rides in tok). The spec
        # step must emit it before verifying past it.
        self.tok_pending = False

    @property
    def out(self) -> "queue.Queue":
        return self.ticket.out


class _Fill:
    """A slot mid-chunked-prefill: the prompt lands piece by piece at
    boundaries; the row emits nothing until the last piece flips it to a
    decoding _Row. ``filled`` is the count of REAL prompt tokens whose KV
    is resident (a prefix-cache hit starts it at the stored prefix len)."""

    __slots__ = ("slot", "ids", "n", "samp", "ticket", "filled", "fp")

    def __init__(self, slot: int, ids: list, n: int, samp: dict,
                 ticket: _Ticket, filled: int = 0,
                 fp: tuple | None = None) -> None:
        self.slot = slot
        self.ids = ids
        self.n = n
        self.samp = samp
        self.ticket = ticket
        self.filled = filled
        # the request's poison-quarantine fingerprint, computed once at
        # preparation (pieces dispatch per boundary; re-hashing the whole
        # prompt per piece would be O(prompt) work on the loop's hot path)
        self.fp = fp


class _ChunkJit:
    """The chunk program as ``aot_cache.StoredProgram`` calls it — ``lower``
    and ``__call__``, ``n_steps`` a keyword — with one ``jax.jit`` a static
    depth, each named for it: the XLA module of a depth-4 dispatch is
    ``jit__chunk_impl_d4``, so a device trace says by itself how many decode
    steps each run of the program made (runs x chunk_size x depth)."""

    def __init__(self, impl, chunk_size: int) -> None:
        self._impl, self._chunk_size = impl, chunk_size
        self._jits: dict[int, object] = {}

    def _jit(self, n_steps: int | None):
        n_steps = n_steps or self._chunk_size
        jit = self._jits.get(n_steps)
        if jit is None:
            impl = self._impl

            def chunk(*args):
                return impl(*args, n_steps=n_steps)

            depth, rest = divmod(n_steps, self._chunk_size)
            chunk.__name__ = chunk.__qualname__ = (
                f"_chunk_impl_d{depth}" if not rest else f"_chunk_impl_s{n_steps}")
            jit = self._jits[n_steps] = jax.jit(chunk, donate_argnums=(1, 2))
        return jit

    def lower(self, *args, n_steps: int | None = None):
        return self._jit(n_steps).lower(*args)

    def __call__(self, *args, n_steps: int | None = None):
        return self._jit(n_steps)(*args)


class ContinuousBatcher:
    """Iteration-level scheduler over a fixed slot array.

    ``submit_row`` enqueues one prompt row; the engine thread admits it into
    a free slot at the next chunk boundary and its output queue receives
    np int32 arrays of new tokens (totalling exactly ``max_new_tokens``),
    then the ``_DONE`` sentinel. ``generate`` / ``stream`` are the blocking
    conveniences the serving layer uses.
    """

    def __init__(self, server, max_slots: int = 8, chunk_size: int = 8,
                 max_len: int = 0, prefix_cache=None, page_size: int = 0,
                 max_live_tokens: int = 0, speculative_k: int = 0,
                 max_ngram: int = 3, paged_attention: str = "gather",
                 pipeline_depth: int = 2,
                 dispatch_depth: int = 0,
                 burst_window_ms: float = 1.0,
                 prefill_chunk: int = 0,
                 prefill_budget: int = 0,
                 max_queue_depth: int = 0,
                 request_timeout_s: float = 0.0,
                 supervise: bool = True,
                 restart_backoff_s: float = 0.25,
                 max_crashes: int = 5,
                 crash_window_s: float = 60.0,
                 boundary_watchdog_s: float = 0.0,
                 flight_recorder: bool = True,
                 flightrec_capacity: int = 0,
                 flight_dump_dir: str = "",
                 device_telemetry: bool = True,
                 allocate: bool = True) -> None:
        if server.family.decode_fns is None:
            raise ValueError(f"family {server.family.name} has no cached decode")
        self.server = server
        self.max_slots = int(max_slots)
        self.chunk_size = int(chunk_size)
        self.max_len = int(max_len) or int(server.max_seq_len)
        # chunked prefill: prompts longer than one piece land piece by
        # piece at boundaries instead of as one monolithic admission
        # prefill (0 = off, today's single-program admission for every
        # prompt). Pieces are 16-bucketed like every compiled prompt shape.
        self.prefill_chunk = pad_seq_len(int(prefill_chunk)) if prefill_chunk else 0
        # per-boundary token budget: decode rows spend chunk_size each
        # first, prefill pieces pack into the remainder (0 = uncapped —
        # every filling row lands one piece per boundary). The HEAD piece
        # always lands regardless, so fills can't starve under a budget
        # smaller than the decode spend.
        self.prefill_budget = int(prefill_budget)
        # prompt-lookup speculation INSIDE the engine (speculative_k > 0):
        # whenever exactly one greedy row is active, the loop swaps the
        # chunk program for a [max_slots, k+1] verify step — propose k
        # tokens from the row's own n-gram history, verify them in ONE
        # device call, accept the agreeing prefix (token-exact by argmax
        # determinism, like models/speculative.py). More than one active
        # row (or a sampled one) falls back to pipelined chunks, where
        # cross-row batching is the better use of each weight read.
        self.speculative_k = int(speculative_k)
        self.max_ngram = int(max_ngram)
        # a verify block writes up to k+1 positions past a row's offset;
        # the per-row cache span must cover whichever engine writes deepest
        self._overrun = max(self.chunk_size, self.speculative_k + 1)
        # models/decode.PrefixKVCache: admissions whose prompt extends a
        # stored prefix prefill only the suffix (multi-turn chat fast path)
        self.prefix_cache = prefix_cache
        self._fwd, self._init_cache = server.family.decode_fns(
            server.cfg, mesh=server.mesh
        )
        self.mesh = server.mesh
        self.mesh_devices = int(self.mesh.size)
        self.stats = {"chunks": 0, "admitted": 0, "active_peak": 0,
                      "prefill_pieces": 0, "stall_ms_max": 0.0,
                      "engine_restarts": 0, "shed": 0, "expired": 0,
                      # admissions decoded from registry-installed prefix
                      # KV (dl/kv_store.py) rather than local prefill
                      "prefix_hits_installed": 0,
                      # pipelined dispatch: device programs launched
                      # ("chunks" stays chunk-EQUIVALENTS — a depth-D
                      # program counts D), the deepest program used, the
                      # worst steady-decode boundary's blocking sync count
                      # (must stay <= 1: the one lagged token readback),
                      # and the high-water planned-but-undelivered tokens
                      "dispatches": 0, "dispatch_depth_max": 1,
                      "host_syncs_per_boundary": 0,
                      "tokens_in_flight_peak": 0, "sync_lag_chunks_max": 0,
                      # pad accounting (ISSUE 17): every dispatched decode
                      # program computes max_slots rows regardless of how
                      # many are live — decode_pad_rows / decode_rows is
                      # the row-padding tax snapshot() exposes as
                      # pad_fraction (admit_pad_rows covers the admit-side
                      # pow2 burst rounding separately)
                      "decode_rows": 0, "decode_pad_rows": 0,
                      # the row-step ledger: every one of the max_slots x
                      # n_steps row-steps a chunk program computes, in exactly
                      # one of the five (_count_row_steps); total is their sum
                      "row_steps": dict.fromkeys(
                          ("tokens", "edge", "filling", "vacant_queued",
                           "vacant_idle", "total"), 0)}
        # where the slots' KV lives (dl/kv_layout.py): [max_slots, max_len]
        # rows, or — page_size > 0 — a pool of pages sized by
        # max_live_tokens and read by gather or in place (paged_attention).
        # Nothing below asks which it got.
        self.kv = kv_layout.build(
            server, self._fwd, self._init_cache, self.stats,
            max_slots=self.max_slots, max_len=self.max_len,
            chunk_size=self.chunk_size, page_size=int(page_size),
            max_live_tokens=max_live_tokens, paged_attention=paged_attention,
            prefix_cache=prefix_cache, prefill_chunk=self.prefill_chunk,
            speculative_k=self.speculative_k)
        # allocate=False leaves the device alone: an engine built by a load
        # while the weights still stream gets its arrays from
        # ``allocate_device_state`` once they are placed
        self._cache = self._tok = None
        try:
            if allocate:
                self.allocate_device_state()
        except BaseException:
            # a RESOURCE_EXHAUSTED here may leave SOME per-layer pools
            # already allocated: drop the partial tree before re-raising
            # so the caller's demote-and-retry (ServerSet.continuous_for)
            # sees those bytes actually returned to the device
            self._cache = None
            self._tok = None
            raise
        # host-side per-slot state (tiny vectors, traced as inputs)
        self._offsets = np.zeros(self.max_slots, np.int32)
        self._steps = np.zeros(self.max_slots, np.int32)
        self._temp = np.zeros(self.max_slots, np.float32)
        self._top_k = np.zeros(self.max_slots, np.int32)
        self._top_p = np.ones(self.max_slots, np.float32)
        self._seeds = np.zeros(self.max_slots, np.int32)
        self._use_filters = np.zeros(self.max_slots, bool)
        self._rows: dict[int, _Row] = {}  # slot -> active row
        self._free = list(range(self.max_slots))
        self._first_pending: list = []  # (row, async first-token array, done)
        self._filling: dict[int, _Fill] = {}  # slot -> chunk-prefilling row
        self._fill_order: list[int] = []  # fill slots, arrival order (FIFO)
        # fills preempted for pages: parked (not re-queued) until a fill
        # flips or dies, else their restart would re-grab the very pages
        # the older fill is blocked on (admit/preempt livelock)
        self._preempted: list = []
        self._last_chunk_t: float | None = None  # stall_ms_max tracking
        # -- pipelined-dispatch bookkeeping ---------------------------------
        # boundary-prep overlap memo: ticket -> (fingerprint, prefix hit),
        # computed by _overlap_prep while chunks execute, consumed (popped)
        # by _gather_prep/_prepare_admit at the admission boundary
        self._prep_memo: dict = {}
        # host copy of the device tok vector's LOOKAHEAD tokens: every
        # chunk program returns its final carry as an extra token column,
        # so the spec-mode transition reads the value from the already-
        # fetched block instead of a blocking device sync. None = stale
        # (a dispatch/admission has advanced tok since the last delivery).
        self._tok_host: np.ndarray | None = None
        from collections import deque as _deque

        # per-boundary host time (dispatch-to-dispatch gap minus the time
        # blocked fetching tokens) — snapshot() serves p50/p99 off this
        self._boundary_host_ms: "_deque[float]" = _deque(maxlen=512)
        self._sync_wait_s = 0.0  # blocking-fetch time since the last dispatch
        self._boundary_syncs = 0  # device->host syncs since the last dispatch
        self._steady = False  # True = no admission/fill/spec since dispatch
        self._tokens_in_flight = 0  # planned-but-undelivered tokens
        self._inflight_chunks = 0  # dispatched-but-unsynced chunk equivalents
        self._depth_last = 1

        # every program below is called through the node's executable store
        # (dl/aot_cache.py): a variant this node has run before is loaded by
        # a key taken BEFORE tracing — everything the programs share is
        # hashed here, once; a call adds its name, its static arguments and
        # the shapes of the arguments that vary
        from modelx_tpu.dl.serve import compile_cache_dir

        program = functools.partial(aot_cache.StoredProgram, aot_cache.ExecutableStore(
            compile_cache_dir(), self.mesh, server.family.name, repr(server.cfg),
            aot_cache.describe_sds(
                getattr(server, "_param_sds", None) or server.params),
            self.kv.describe(), self.chunk_size, self.prefill_chunk,
            self.speculative_k, prefix_cache is not None))
        # admission is ONE program (prefill + first token + insert-at-slot):
        # every call costs a host dispatch round-trip, so the two-call
        # prefill-then-insert shape would double admission latency.
        # Without a prefix cache the scratch KV stays internal (no output
        # buffer materialized just to be dropped on the host).
        if prefix_cache is None:
            def _admit_nosmall(*args):
                return self._admit_impl(*args)[:3]  # drop the scratch KV output

            admit = jax.jit(_admit_nosmall, donate_argnums=(2, 3))
        else:
            admit = jax.jit(self._admit_impl, donate_argnums=(2, 3))
        self._admit_prog = program("admit", admit, described=1)
        # prefix-hit variant: stored KV rides in as an argument (never
        # donated — the cache entry outlives the admission); trim_len is
        # static so stored entries stay bucketed to the PROMPT's bucket
        # (entries must not grow by a bucket per conversation turn)
        self._admit_cached_prog = program("admit_cached", jax.jit(
            self._admit_cached_impl, static_argnums=(12,), donate_argnums=(2, 3),
        ), static_argnums=(12,), described=1)
        # batched admission (same-bucket burst arrivals -> one program);
        # engaged only without a prefix cache — the cached path's per-row
        # scratch-KV returns would cost k x leaves slice dispatches, and
        # multi-turn conversations rarely arrive as same-instant bursts
        self._admit_many_prog = program("admit_many", jax.jit(
            self._admit_many_impl, donate_argnums=(2, 3),
        ), described=1)
        # ONE chunk callable for every dispatch depth: n_steps is a STATIC
        # argument (one compiled variant per depth actually used), so the
        # fault-injection seam (tests/bench wrap self._chunk) and the
        # env-gated chaos wrap below cover deep programs too. A load fetches
        # the variant every first request runs ahead of it (chunk_warmer).
        self._chunk_prog = program("chunk", _ChunkJit(
            self._chunk_impl, self.chunk_size), described=1)
        self._chunk = self._chunk_prog
        # chunked-prefill piece programs: a mid piece only advances the
        # slot's KV (no logits output -> XLA drops the lm_head matmul);
        # the flip (last) piece also samples the row's first token.
        # Compiled once per piece bucket, like every other prompt shape.
        self._piece_prog = program("piece", jax.jit(
            self._piece_impl, donate_argnums=(2,)), described=1)
        self._piece_flip_prog = program("piece_flip", jax.jit(
            self._piece_flip_impl, donate_argnums=(2, 3),
        ), described=1)
        # prefix-hit fill seeding: copy a stored prefix KV into the slot
        # so only the suffix chunk-prefills (stored entry never donated —
        # it outlives the admission)
        self._seed_prog = program("seed", jax.jit(
            self._seed_impl, donate_argnums=(0,)))
        # flip-time prefix store: slice the freshly filled prompt KV back
        # out of the slot (a copy — the live row decodes on)
        self._snap_prog = program("snap", jax.jit(
            self._snap_impl, static_argnums=(2,)), static_argnums=(2,))
        # chunks the loop keeps in flight before syncing the oldest: plans
        # are value-independent (budgets only), so depth-D dispatch is
        # exact; it hides the per-chunk fetch round-trip behind device
        # compute. Value-DEPENDENT row exits (stop tokens, client cancels)
        # lag by up to depth chunks of wasted compute, never wrong tokens.
        self.pipeline_depth = max(1, int(pipeline_depth))
        # decode steps per device program, in CHUNKS: when every slot is in
        # steady decode (nothing queued/waiting/filling, no first token
        # owed) one program scans depth x chunk_size steps, amortizing the
        # fixed per-dispatch cost depth-fold. 0 = auto (AUTO_DISPATCH_DEPTH
        # in steady decode); 1 = classic per-chunk dispatch. Stop/cancel/
        # deadline detection lags by the program's span (wasted compute,
        # never wrong tokens: the (seed, step) streams are schedule-
        # invariant); _pick_depth also caps depth at every row's remaining
        # budget so writes stay inside the validated _overrun span.
        self.dispatch_depth = int(dispatch_depth)
        if self.dispatch_depth < 0:
            raise ValueError("dispatch_depth must be >= 0 (0 = auto)")
        self._depth_cap = self.dispatch_depth or self.AUTO_DISPATCH_DEPTH
        # idle-burst gather window: when the first request hits an IDLE
        # engine, wait this long for co-arrivals before admitting (burst ->
        # one admit program + aligned decode depths). 0 disables.
        self.burst_window_ms = float(burst_window_ms)
        self._spec_prog = program("spec_verify", jax.jit(
            self._spec_verify_impl, donate_argnums=(1,)), described=1)

        self._q: "queue.Queue" = queue.Queue()
        # FIFO admission backlog: items popped from the queue while no slot
        # was free wait HERE (in arrival order) — re-putting them at the
        # back of the queue would let later arrivals jump them under slot
        # contention
        self._waiting: list = []
        self._closed = False
        self._broken: BaseException | None = None
        self._close_lock = threading.Lock()
        # -- bounded admission + deadlines ----------------------------------
        # max_queue_depth > 0: submits past this many not-yet-admitted rows
        # shed with QueueFullError (429 + Retry-After on the wire) instead
        # of queueing into unbounded latency. _backlog counts rows in _q +
        # _waiting + _preempted, maintained under _close_lock.
        self.max_queue_depth = int(max_queue_depth)
        # request_timeout_s > 0: every submit gets a deadline; the loop
        # expires past-deadline rows at chunk boundaries (waiting, filling,
        # or decoding) with DeadlineExceededError (504 on the wire)
        self.request_timeout_s = float(request_timeout_s)
        self._backlog = 0
        # -- supervision ----------------------------------------------------
        # a crashed loop no longer bricks the engine: after the death path
        # drains every waiter, the supervisor (_run's outer loop) rebuilds
        # the device state and restarts, with exponential crash-loop
        # backoff; more than max_crashes crashes inside crash_window_s
        # opens the circuit (stay broken — something is systematically
        # wrong and restart livelock would just burn the device)
        self.supervise = bool(supervise)
        self.restart_backoff_s = float(restart_backoff_s)
        self.max_crashes = int(max_crashes)
        self.crash_window_s = float(crash_window_s)
        self._crash_times: list[float] = []
        self._restarts = 0
        self._state = "running"  # running | restarting | broken | stopped
        self._closed_ev = threading.Event()  # interrupts the backoff sleep
        # poison quarantine: fingerprint -> count of loop crashes that
        # happened while dispatching THAT request's admission/fill work; at
        # POISON_CRASHES the request is rejected at submit with 400 instead
        # of being re-admitted into another crash
        self._poison: dict[tuple, int] = {}
        self._suspect_fp: tuple | None = None
        # -- hang watchdog --------------------------------------------------
        # boundary_watchdog_s > 0: a monitor thread treats a boundary that
        # makes no progress for this long (while rows are active) as a
        # crash — the supervisor only heals crashes, and a WEDGED device
        # dispatch (real on TPU: a hung transfer or collective) would
        # otherwise hold the loop, and every waiter, forever. Off by
        # default: first-touch XLA compiles legitimately take seconds, so
        # the operator picks a window that clears them.
        self.boundary_watchdog_s = float(boundary_watchdog_s)
        self._watch_stall: BaseException | None = None
        self._progress_t: float | None = None
        # -- flight recorder (ISSUE 15) -------------------------------------
        # bounded ring of boundary-granularity engine events (admission,
        # fill piece, dispatch, readback, preemption, EOS, expiry, stall,
        # crash) — the black box the supervisor dumps on crash/watchdog/
        # circuit-break so healing stops destroying the evidence. On by
        # default: the per-boundary cost is a few dict stores (the bench's
        # flightrec_overhead_pct leg holds the tax under 2%).
        self.flight_dump_dir = str(flight_dump_dir or "")
        self.flightrec = (
            flightrec.FlightRecorder(
                int(flightrec_capacity) or flightrec.DEFAULT_CAPACITY)
            if flight_recorder else None
        )
        # the request whose admission/fill dispatch is in flight, for crash
        # attribution in the dump (the id twin of _suspect_fp)
        self._suspect_rid = ""
        # measured device telemetry (utils/devmem) sampled into snapshot()
        self.device_telemetry = bool(device_telemetry)
        # windowed token rate (tokens/s over 1m/5m) fed at delivery time
        self.rate_tokens = tswheel.Wheel()
        # per-request latency histograms (ISSUE 13): fed at first-token
        # delivery from the ticket's phase stamps; snapshot() exposes them
        # once populated and the Prometheus exposition renders them as
        # explicit-bucket histogram families
        self.hist_queue_ms = promexp.Histogram()
        self.hist_ttft_ms = promexp.Histogram()
        # env-gated chaos drills (default off): MODELX_FAULT_PLAN schedules
        # deterministic dispatch faults against the running engine
        env_plan = _faults.from_env()
        if env_plan is not None and env_plan.has("engine.dispatch"):
            self._chunk = _faults.wrap_dispatch(self._chunk, env_plan)
        if self.prefill_chunk > 0:
            self.stats["prefill_chunk"] = self.prefill_chunk
            self.stats["fill_waits"] = 0  # page-blocked boundaries
            self.stats["fill_preempts"] = 0  # fills restarted for pages
            # pieces dispatched and the prompt tokens they landed
            self.stats["fill"] = {"pieces": 0, "tokens": 0}
        if self.boundary_watchdog_s > 0:
            self.stats["watchdog_stalls"] = 0
        self._phases = trace.Phases("continuous.boundary", _PHASES)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        if self.boundary_watchdog_s > 0:
            self._watch_thread = threading.Thread(
                target=self._watchdog, daemon=True
            )
            self._watch_thread.start()

    # a request is quarantined once this many loop crashes are attributed
    # to dispatching its admission/fill work
    POISON_CRASHES = 2

    # dispatch_depth=0 resolves to this in steady decode: deep enough to
    # amortize the fixed dispatch round-trip, shallow enough that a
    # streaming client's flush cadence (delivery still splits into
    # chunk_size pieces) and the stop-detection lag stay bounded
    AUTO_DISPATCH_DEPTH = 4

    def allocate_device_state(self) -> None:
        """Allocate the KV state and the token buffer, zeroed, on the
        serving mesh. The engine owns this state and donates it through
        every program, so HBM holds exactly one copy."""
        self._cache = self.kv.new_state()
        self._tok = jnp.zeros((self.max_slots, 1), jnp.int32)

    # -- flight recorder ------------------------------------------------------

    def _rec(self, event: str, slot: int = -1, request_id: str = "",
             **fields) -> None:
        """Record one engine event into the flight ring (no-op when the
        recorder is disabled)."""
        fr = self.flightrec
        if fr is not None:
            fr.record(event, slot=slot, request_id=request_id, **fields)

    def _slot_states(self) -> list[dict]:
        """Per-slot occupancy for the black-box dump: who held which slot
        (and how far along) when the engine died."""
        out = []
        for slot, row in list(self._rows.items()):
            out.append({"slot": slot, "state": "decoding",
                        "request_id": row.ticket.request_id,
                        "emitted": row.emitted, "budget": row.budget})
        for slot, fill in list(self._filling.items()):
            out.append({"slot": slot, "state": "filling",
                        "request_id": fill.ticket.request_id,
                        "filled": fill.filled,
                        "prompt_len": len(fill.ids)})
        return out

    def _flight_dump(self, reason: str, err: BaseException | None) -> str:
        """Write the black-box file (crash / watchdog / circuit-break).
        Best-effort by design: the engine is already dying, and the dump
        path must never add a failure mode of its own."""
        if self.flightrec is None or not self.flight_dump_dir:
            return ""
        meta = {
            "model": str(getattr(self.server, "name", "") or ""),
            "engine_state": self._state,
            "restarts": self._restarts,
        }
        if err is not None:
            meta["error"] = repr(err)[:300]
        path = self.flightrec.dump(
            self.flight_dump_dir, reason, meta=meta,
            slots=self._slot_states(),
        )
        if path:
            logging.getLogger("modelx.serve").warning(
                "flight recorder dumped %s black box to %s", reason, path
            )
        return path

    # -- compiled programs ----------------------------------------------------

    def _sample_first(self, logits, last_idx, temp, top_k, top_p, seed,
                      step=0):
        """Each row's first token: step ``step`` of its sample stream (0
        for a fresh request; a RESUMED request that re-prefilled
        prompt + k emitted tokens continues at step k, so the token is
        byte-identical to the one the interrupted stream would have
        emitted next). Row-wise: works for the [1, S] single admission
        and the [k, S] batched admission alike."""
        from modelx_tpu.ops import sampling as sampling_ops

        idx = jnp.broadcast_to(
            last_idx[:, None, None], (logits.shape[0], 1, logits.shape[-1])
        )
        last = jnp.take_along_axis(logits, idx, axis=1)[:, 0, :]
        return sampling_ops.sample(
            last.astype(jnp.float32), jax.random.PRNGKey(0), temp,
            top_k=top_k, top_p=top_p, seeds=seed, step=step,
        )

    def _admit_many_impl(self, params, prompts, cache, tok, row_lens, where,
                         temp, top_k, top_p, seeds, first_steps):
        """A burst of same-bucket admissions as ONE program: prefill the
        [m, Sb] block into a fresh scratch cache, sample every row's first
        token (step 0 of its own seed stream — identical to k single
        admits), and write the scratch rows to their slots. Each program
        dispatch costs a host round-trip, so k arrivals admitted one-by-one
        pay k round-trips where this pays one. The host pads the burst to
        the next POWER OF TWO of its size (pad rows carry an out-of-bounds
        slot index, which the layout's write and the tok scatter's
        ``mode="drop"`` discard), so small bursts don't pay a full
        max_slots-row prefill and compiles stay bounded at log2(max_slots)
        sizes per prompt bucket."""
        small = self._init_cache(prompts.shape[0], prompts.shape[1])
        logits, small = self._fwd(params, prompts, kv_cache=small, cache_offset=0,
                                  **self.kv.block_kwargs(row_lens))
        firsts = self._sample_first(logits, row_lens - 1, temp, top_k, top_p,
                                    seeds, step=first_steps)
        cache = self.kv.put_many(cache, small, where)
        tok = tok.at[self.kv.slot_of(where), 0].set(firsts, mode="drop")
        return cache, tok, firsts

    def _finish_admit(self, small, logits, cache, tok, last_idx, where,
                      temp, top_k, top_p, seed, first_step):
        """Shared admit tail: sample the row's first token and write the
        scratch cache + token to the slot ``where`` names in the donated
        engine state. Returns (cache, tok, first, small) — ``small`` goes
        back to the host for the prefix cache."""
        first = self._sample_first(logits, last_idx, temp, top_k, top_p, seed,
                                   step=first_step)
        cache = self.kv.put(cache, small, where)
        tok = jax.lax.dynamic_update_slice(
            tok, first[:, None], (self.kv.slot_of(where), 0))
        return cache, tok, first, small

    def _admit_impl(self, params, prompt, cache, tok, row_len, where,
                    temp, top_k, top_p, seed, first_step):
        """One program per admission: prefill the [1, S] prompt into a
        scratch cache (allocated INSIDE the jit — zeros fuse, no host
        transfer), then the shared admit tail."""
        small = self._init_cache(1, prompt.shape[1])
        logits, small = self._fwd(params, prompt, kv_cache=small, cache_offset=0,
                                  **self.kv.block_kwargs(row_len))
        return self._finish_admit(small, logits, cache, tok, row_len - 1, where,
                                  temp, top_k, top_p, seed, first_step)

    def _admit_cached_impl(self, params, suffix, cache, tok, suffix_len, plen,
                           where, stored, temp, top_k, top_p, seed,
                           trim_len: int, first_step=0):
        """Prefix-hit admission: the scratch cache starts as the STORED
        prefix KV (extended with zeros for the suffix bucket) and only the
        [1, Sb] suffix block prefills, at offset ``plen``. KV values are a
        deterministic function of the token prefix, so the admitted row is
        byte-identical to a full prefill. Junk in the stored bucket past
        the real prefix is overwritten by the suffix write (each layer
        writes its k/v BEFORE attending), and junk past the suffix span
        sits beyond every query position until decode overwrites it.
        ``trim_len`` (static, = the full prompt's 16-bucket) cuts the
        scratch back down before insertion/storage."""
        sb = suffix.shape[1]
        small = jax.tree_util.tree_map(
            lambda s: jnp.concatenate(
                [s, jnp.zeros((1, sb) + s.shape[2:], s.dtype)], axis=1
            ),
            stored,
        )
        logits, small = self._fwd(params, suffix, kv_cache=small, cache_offset=plen)
        small = jax.tree_util.tree_map(lambda c: c[:, :trim_len], small)
        return self._finish_admit(small, logits, cache, tok, suffix_len - 1, where,
                                  temp, top_k, top_p, seed, first_step)

    # -- chunked prefill piece programs ---------------------------------------

    def _piece(self, params, piece, cache, filled, where, last_idx=None):
        """Land one [1, Sb] prefill piece: view the slot's own [1, max_len]
        rows — a mid-prompt piece needs the row's earlier KV as attention
        context, unlike admission's fresh offset-0 scratch — run the block
        at offset ``filled`` (positions/causality follow the decode
        contract, so the landed KV is byte-identical to the same span of a
        monolithic prefill), write back what it wrote. ``last_idx``: the
        piece's last real token where its bucket is padded (the last piece)."""
        row = self.kv.view(cache, where, self.max_len)
        logits, row = self._fwd(params, piece, kv_cache=row, cache_offset=filled,
                                **self.kv.block_kwargs(last_idx=last_idx))
        return logits, self.kv.put_piece(cache, row, where)

    def _piece_impl(self, params, piece, cache, filled, where):
        """One mid-prompt piece. Logits are not an output — XLA drops the
        lm_head matmul for mid pieces."""
        return self._piece(params, piece, cache, filled, where)[1]

    def _piece_flip_impl(self, params, piece, cache, tok, filled, where,
                         last_idx, temp, top_k, top_p, seed, first_step):
        """The LAST piece: land its KV and sample the row's first token
        from the piece's final real position — step ``first_step`` of the
        row's (seed, step) stream (0 fresh, k on resume), byte-identical
        to single-program admission."""
        logits, cache = self._piece(params, piece, cache, filled, where, last_idx)
        first = self._sample_first(logits, last_idx, temp, top_k, top_p, seed,
                                   step=first_step)
        tok = jax.lax.dynamic_update_slice(
            tok, first[:, None], (self.kv.slot_of(where), 0))
        return cache, tok, first

    def _seed_impl(self, cache, stored, where):
        """Prefix-hit fill seeding: the stored [1, plen-bucket] prefix KV
        lands at the slot's offset 0. Bucket junk past the real prefix is
        overwritten by the first suffix piece (each layer writes its k/v
        before attending, and piece >= 16 > bucket - plen)."""
        return self.kv.put(cache, stored, where)

    def _snap_impl(self, cache, where, bucket: int):
        """Copy the slot's freshly filled prompt KV back out (prefix-cache
        store at flip time; the live row decodes on, so this is a copy)."""
        return self.kv.view(cache, where, bucket)

    def _chunk_impl(self, params, cache, tok, *args, n_steps=None):
        """``n_steps`` decode steps over ALL slots (``n_steps`` is STATIC —
        the default is one ``chunk_size`` chunk, a depth-D dispatch passes
        D x chunk_size). ``args`` is what ``_chunk_args`` lists: whatever
        the layout needs to find every slot (dense: nothing), then
        offsets, steps, temp, top_k, top_p, seeds — offsets/steps are
        per-row (slots joined at different times sit at different depths),
        and idle slots decode garbage that the layout keeps harmless (their
        own unused rows, or the trash page). ``top_k``/``top_p``
        arrive as None when NO active row uses filters — the None variant
        compiles without the per-step full-vocab sort the filters need
        (jit caches both variants; values are identical either way since
        0 / 1.0 mean "off" per row). The token block carries one EXTRA
        trailing column: the scan's final carry (each row's next,
        not-yet-delivered token), so the host's lagged readback also
        learns the lookahead value without a second device sync."""
        from modelx_tpu.ops import attention as attn_ops
        from modelx_tpu.ops import sampling as sampling_ops

        *where, offsets, steps, temp, top_k, top_p, seeds = args
        # a layout that keeps states is told which rows decode (from the
        # dispatch's own offsets and steps): an idle or filling slot's state
        # must come out of the scan as it went in. The others are told nothing
        told = self.kv.step_kwargs(offsets, steps)

        def step_fn(carry, _i):
            cache, tok, offsets, steps = carry
            with attn_ops.ragged_calls() as ragged:
                logits, cache = self.kv.step(params, tok, cache, offsets, *where, **told)
            # where layers of this step took the ragged kernel, the KV
            # positions their blocks cover and the positions cached, over
            # all slots; None (nothing is added to the program) where none did
            kv_read = attn_ops.kv_positions(ragged, offsets + 1) if ragged else None
            nxt = sampling_ops.sample(
                logits[:, -1, :].astype(jnp.float32), jax.random.PRNGKey(0), temp,
                top_k=top_k, top_p=top_p, seeds=seeds, step=steps,
            )
            return (cache, nxt[:, None], offsets + 1, steps + 1), (tok[:, 0], kv_read)

        (cache, tok, offsets, steps), (toks, kv_read) = jax.lax.scan(
            step_fn, (cache, tok, offsets, steps),
            jnp.arange(n_steps or self.chunk_size),
        )
        return cache, tok, self.kv.ride(
            cache, jnp.concatenate([toks.T, tok], axis=1), kv_read)

    def _chunk_args(self, filtered: bool) -> list:
        """The per-slot inputs of one chunk dispatch, after params, cache
        and tok. Filters only when an ACTIVE row asked: the None variant
        skips the per-step full-vocab sort (retired slots' stale values are
        garbage rows whose tokens are discarded anyway)."""
        # .copy() is load-bearing: jax zero-copy-aliases host numpy
        # buffers (CPU backend) and transfers lazily, while the loop
        # mutates the originals (retirement resets, next admissions)
        # possibly BEFORE the in-flight chunk reads them — each dispatch
        # gets private snapshots nobody mutates
        return [
            *self.kv.all_slots(),
            jnp.asarray(self._offsets.copy()), jnp.asarray(self._steps.copy()),
            jnp.asarray(self._temp.copy()),
            jnp.asarray(self._top_k.copy()) if filtered else None,
            jnp.asarray(self._top_p.copy()) if filtered else None,
            jnp.asarray(self._seeds.copy()),
        ]

    def chunk_warmer(self, param_sds: dict):
        """Reserve the chunk program every first request runs — one chunk
        deep, no filters: its shapes are ``max_slots``, ``max_len`` and
        ``chunk_size``, nothing a request brings — and return the work that
        fetches it, for a side thread of the load: from the node's program
        store, else traced, lowered and compiled, from the abstract weights
        and the abstract state of this engine — which need not be allocated
        yet — described as a first dispatch meets it, so that its key, and
        the persistent cache's, is the one that dispatch would produce. A
        dispatch that comes while the side thread still holds the program
        waits for it. The work returns how many programs it delivered."""
        # a dispatch meets the engine's state as the admit program returned
        # it — committed to the mesh — not as jnp.zeros left it
        tok = jax.ShapeDtypeStruct((self.max_slots, 1), jnp.int32,
                                   sharding=kv_layout.replicated(self.mesh))
        return self._chunk_prog.prefetch(
            param_sds, self.kv.abstract_state(), tok, *self._chunk_args(False),
            n_steps=self.chunk_size)

    # -- speculative verify (single-occupied greedy slot) ---------------------

    def _spec_verify_impl(self, params, cache, block, *args):
        """One verify step over the engine's FULL slot array: ``block`` is
        [max_slots, k+1] (the active slot carries last-token + proposals;
        idle slots carry zeros whose writes land at their offset-0 garbage
        rows); ``args`` is the layout's every-slot argument, if any, then
        the offsets. Returns the model's argmax at every position —
        position i is its pick for the token AFTER block[:, :i+1]. Rejected
        positions leave garbage KV; the host rewinds offsets past them, and
        the causal mask (kpos <= qpos) hides them until overwritten."""
        *where, offsets = args
        logits, cache = self.kv.step(params, block, cache, offsets, *where)
        return cache, jnp.argmax(logits, axis=-1)  # [max_slots, k+1]

    def _spec_ok(self) -> bool:
        """Speculate iff exactly one greedy row is active and nothing is
        waiting for a slot (admissions beat speculation — cross-row
        batching uses each weight read better than lookahead does). A
        filling row also disqualifies: its pieces need boundaries."""
        if (self.speculative_k <= 0 or len(self._rows) != 1
                or self._waiting or self._filling):
            return False
        row = next(iter(self._rows.values()))
        return (row.greedy and not row.closed and not row.ticket.cancelled
                and row.seq is not None)

    def _spec_step(self) -> None:
        """Propose -> verify -> accept -> deliver, synchronously (the spec
        regime trades the chunk pipeline's depth for fewer device steps per
        token; it only runs when there is no other row to pipeline with).

        Block convention: the engine invariant says the cache holds
        [0, offsets) and ``tok`` carries the next token to CONSUME. After
        admission that token (the prefill's first) is already delivered;
        after a chunk it is the chunk's lookahead token, not yet delivered
        (``row.tok_pending``) — the step emits it as part of this round's
        piece. Either way the verify block is [that token, proposals...] at
        the row's offset, exactly models/speculative.py's layout."""
        from modelx_tpu.models.speculative import _NgramIndex

        slot, row = next(iter(self._rows.items()))
        prefix_emit: list[int] = []
        if row.tok_pending:
            # the lookahead token rides in the last delivered chunk's extra
            # carry column (_tok_host) — the chunk->spec transition costs
            # NO extra device sync. The fallback sync only fires when no
            # delivery refreshed the host copy (shouldn't happen: the loop
            # drains every in-flight chunk before entering spec mode).
            if self._tok_host is not None:
                tok_val = int(self._tok_host[slot])
            else:
                t0 = time.monotonic()
                tok_val = int(np.asarray(self._tok)[slot, 0])
                self._sync_wait_s += time.monotonic() - t0
                self._boundary_syncs += 1
            row.seq.append(tok_val)
            prefix_emit = [tok_val]
        else:
            tok_val = row.seq[-1]
        if row.ngram is None:
            row.ngram = _NgramIndex(self.max_ngram)
        row.ngram.extend(row.seq, row.ng_len)
        row.ng_len = len(row.seq)
        k = self.speculative_k
        prop = row.ngram.propose(row.seq, k)
        block = np.zeros((self.max_slots, k + 1), np.int32)
        block[slot, 0] = tok_val
        if prop:
            block[slot, 1:1 + len(prop)] = prop
        with trace.span("continuous.spec_verify", proposed=len(prop)):
            self._cache, argm_dev = self._spec_prog(
                self.server.params, self._cache, jnp.asarray(block),
                *self.kv.all_slots(), jnp.asarray(self._offsets.copy()),
            )
        # THE spec boundary's one blocking readback (verify is inherently
        # synchronous: acceptance decides the next proposal)
        t0 = time.monotonic()
        argm = np.asarray(argm_dev)[slot]
        self._sync_wait_s += time.monotonic() - t0
        self._boundary_syncs += 1
        self.stats["spec_steps"] = self.stats.get("spec_steps", 0) + 1
        self.stats["spec_proposed"] = self.stats.get("spec_proposed", 0) + len(prop)
        # accept while the model agrees, then its own token at the first
        # disagreement (exactly models/speculative.py's greedy rule)
        a = 0
        while a < len(prop) and int(argm[a]) == prop[a]:
            a += 1
        room = row.budget - row.emitted
        new = (prefix_emit + prop[:a] + [int(argm[a])])[:room]
        verified = new[len(prefix_emit):]  # tokens the verify itself emitted
        self.stats["spec_accepted"] = (
            self.stats.get("spec_accepted", 0) + min(a, len(verified))
        )
        # rewind past rejected/padded positions; only verified history stays
        self._offsets[slot] += a + 1
        self._steps[slot] += a + 1
        row.seq.extend(verified)
        row.emitted += len(new)
        # engine state for a possible fall-back to chunk mode: tok carries
        # the row's last DELIVERED token, whose chunk-entry re-emission the
        # skip swallows
        tok_np = np.zeros((self.max_slots, 1), np.int32)
        tok_np[slot, 0] = row.seq[-1]
        self._tok = jnp.asarray(tok_np)
        self._tok_host = tok_np[:, 0].copy()  # spec knows tok on the host
        self._steady = False  # spec rounds aren't steady-decode boundaries
        row.skip = 1
        row.tok_pending = False
        piece = np.asarray([new], np.int32)
        done = row.emitted >= row.budget
        if row.stops:
            from modelx_tpu.models.decode import stop_cut

            cut = stop_cut(new, row.stops)
            if cut is not None:
                piece = piece[:, :cut]
                done = True
        row.out.put(piece)
        if done:
            row.out.put(_DONE)
            row.closed = True  # sweep frees the slot before the next step

    # -- engine loop ----------------------------------------------------------

    def _span(self, ids, n: int) -> int:
        """The row's full write span in tokens (prompt bucket + budget +
        the overrun margin — the same ``need`` submit validates)."""
        return pad_seq_len(len(ids)) + n + self._overrun

    def _admits_now(self, item) -> bool:
        """A free slot — and room in the KV layout. A prompt that will
        single-program-admit needs its whole span up front (a mid-decode
        pool exhaustion must not strand a half-decoded row); a prompt that
        will CHUNK-fill needs only its first piece's — the rest reserves
        incrementally as decode rows retire, so a long prompt's admission
        no longer serializes behind the pool-full FIFO for its full span."""
        if not self._free:
            return False
        ids, n, _samp, ticket = item
        if ticket.cancelled:
            return True  # takes no room: preparation ends it
        if self.prefill_chunk > 0 and pad_seq_len(len(ids)) > self.prefill_chunk:
            return self.kv.fits(self.prefill_chunk)
        return self.kv.fits(self._span(ids, n))

    def _release_slot(self, slot: int) -> None:
        """Return a retired row's slot, and what it had reserved, to the
        free sets."""
        self._free.append(slot)
        self._offsets[slot] = 0
        self.kv.release(slot)

    def _gather_prep(self, item, to_admit: list) -> None:
        """Prepare one admissible item into ``to_admit``. If preparation
        itself dies, every waiter gathered so far (plus this item's) is
        failed before the engine unwinds — their preps live only in the
        loop-local list, out of reach of the generic death failsafes."""
        self._backlog_sub(1)  # leaving the not-yet-admitted set, whatever happens
        # consume the boundary-prep overlap memo (fingerprint + prefix
        # lookup computed while the previous chunks executed); fall back to
        # computing inline for items the overlap pass hadn't reached
        memo = self._prep_memo.pop(item[3], None)
        fp = memo[0] if memo is not None else _fingerprint(item[0], item[1])
        self._suspect_fp = fp
        self._suspect_rid = item[3].request_id
        try:
            prep = self._prepare_admit(
                item, memo_hit=memo[1] if memo is not None else _NO_HIT
            )
        except BaseException as e:
            item[3].out.put(e)
            for p in to_admit:
                p["ticket"].out.put(e)
            raise
        self._suspect_fp = None
        self._suspect_rid = ""
        if prep is not None:
            prep["fp"] = fp  # reused by the admit/fill dispatch attribution
            to_admit.append(prep)

    def _prepare_admit(self, item, memo_hit=_NO_HIT) -> dict | None:
        """Claim a slot (and reserve the row's span) for one
        admissible item and resolve its prefix-cache hit. Pure host-side
        bookkeeping — the device dispatch happens in ``_admit_one`` /
        ``_admit_group`` so a burst of preparations can share a program.

        With chunked prefill on, a prompt whose to-prefill span exceeds
        one piece becomes a FILL preparation instead: the slot is
        claimed but nothing dispatches now — pieces land at boundaries
        (prefix hits seed the fill's offset so only the suffix chunks)."""
        ids, n, samp, ticket = item
        if ticket.cancelled:  # consumer left while the request queued
            ticket.out.put(_DONE)
            return None
        if ticket.deadline is not None and time.monotonic() > ticket.deadline:
            # expired while queued: 504 BEFORE occupying a slot
            self.stats["expired"] += 1
            self._rec("deadline", request_id=ticket.request_id,
                      state="queued")
            ticket.out.put(self._deadline_error(ticket, "waiting for a slot"))
            return None
        slot = self._free.pop()
        if not ticket.t_admit:  # first claim only: restarts re-enter here
            ticket.t_admit = time.monotonic()
        s = len(ids)
        hit = None
        if self.prefix_cache is not None:
            if memo_hit is not _NO_HIT:
                # boundary-prep overlap memoized this lookup while the
                # previous chunks executed (a store racing in since then is
                # only a missed optimization, never a correctness issue)
                hit = memo_hit
            else:
                # fit-aware lookup: entries whose bucket + suffix bucket
                # exceed the slot cache are skipped (shorter fitting
                # prefixes still win)
                hit = self.prefix_cache.lookup(ids, max_total=self.max_len)
        to_fill = s - (hit[0] if hit is not None else 0)
        fill = self.prefill_chunk > 0 and pad_seq_len(to_fill) > self.prefill_chunk
        # a single-program admission reserves the row's WHOLE span now (the
        # admit program only writes the prompt bucket, decode fills the
        # rest). It is refused only with chunked prefill on — a hit can
        # shrink a long prompt under one piece after _admits_now gated on
        # the first-piece estimate — and the row then fills incrementally
        if not fill and not self.kv.reserve(slot, self._span(ids, n)):
            fill = True
        return {"ids": ids, "n": n, "samp": samp, "ticket": ticket,
                "slot": slot, "s": s, "hit": hit, "bucket": pad_seq_len(s),
                "fill": fill, "finished": False}

    def _finish_admit_host(self, prep: dict, first_ref) -> None:
        """Shared post-dispatch bookkeeping: per-slot vectors, the row
        object, and its async first-token delivery. ``first_ref`` is a
        zero-arg callable yielding the row's first token as np [1, 1]."""
        slot, s, samp = prep["slot"], prep["s"], prep["samp"]
        k_val = int(samp.get("top_k", 0))
        p_val = float(samp.get("top_p", 1.0))
        self._offsets[slot] = s
        # a resumed request re-prefilled prompt + k emitted tokens and its
        # first token here was sampled at step k — the row continues the
        # original (seed, step) stream, not a fresh one
        self._steps[slot] = int(samp.get("resume_step", 0)) + 1
        self._temp[slot] = float(samp.get("temperature", 0.0))
        self._top_k[slot] = k_val
        self._top_p[slot] = p_val
        self._seeds[slot] = int(samp.get("seed", 0))
        self._use_filters[slot] = k_val > 0 or p_val < 1.0
        row = _Row(
            slot, prep["n"], prep["ticket"],
            stops=frozenset(samp.get("stop_token_ids") or ()),
            seq=list(prep["ids"]) if self.speculative_k > 0 else None,
            greedy=float(samp.get("temperature", 0.0)) <= 0.0,
        )
        # the prefill's first token is delivered ASYNC (with the next
        # delivery batch): syncing here would serialize a full dispatch
        # round-trip per admission, where dispatching N prefills
        # back-to-back pipelines them
        row.emitted = 1
        done = row.emitted >= row.budget
        self._first_pending.append((row, first_ref, done))
        if done:
            self._release_slot(slot)
        else:
            self._rows[slot] = row
        prep["finished"] = True
        self._steady = False  # an admission boundary, not steady decode
        self.stats["admitted"] += 1
        self.stats["active_peak"] = max(self.stats["active_peak"], len(self._rows))
        self._rec("admit", slot=slot, request_id=prep["ticket"].request_id,
                  prompt_len=s, budget=prep["n"])

    def _admit_all(self, preps: list) -> None:
        """Dispatch a boundary's worth of prepared admissions: same-bucket
        prefix-cache-free preparations share ONE [k, Sb] program, the rest
        go one-by-one. If a dispatch dies mid-batch, every not-yet-finished
        preparation's waiter is failed before the engine unwinds."""
        self._tok_host = None  # admit programs advance the device tok
        try:
            singles: list = []
            groups: dict[int, list] = {}
            for p in preps:
                if p["fill"]:
                    # chunked prefill: no admit program — the fill's
                    # pieces land at boundaries from the engine loop
                    self._start_fill(p)
                elif self.prefix_cache is not None:
                    # single path stores each row's scratch KV (hit or miss)
                    singles.append(p)
                else:
                    groups.setdefault(p["bucket"], []).append(p)
            for group in groups.values():
                if len(group) == 1:
                    singles.append(group[0])
                    continue
                with trace.span("continuous.admit_many", rows=len(group)):
                    self._admit_group(group)
                self.stats["admit_batches"] = (
                    self.stats.get("admit_batches", 0) + 1
                )
            for p in singles:
                with trace.span("continuous.admit"):
                    self._admit_one(p)
        except BaseException as e:
            for p in preps:
                if not p["finished"]:
                    p["ticket"].out.put(e)
            raise

    def _admit_group(self, preps: list) -> None:
        """One program admits the whole same-bucket group as [m, Sb], with
        m the burst size rounded UP to the next power of two (clamped to
        max_slots): a 2-row burst on a max_slots=16 engine used to prefill
        a full [16, Sb] block — up to max_slots/2 x wasted prefill FLOPs
        on small bursts. Pow2 rounding keeps compiles bounded at
        log2(max_slots) sizes per prompt bucket (burst size itself never
        retraces). Rows past the real burst are padded with row_len 1 and
        an out-of-bounds slot index (scatter ``mode="drop"`` discards)."""
        sb = preps[0]["bucket"]
        m = min(self.max_slots, 1 << max(len(preps) - 1, 0).bit_length())
        self.stats["admit_pad_rows"] = (
            self.stats.get("admit_pad_rows", 0) + m - len(preps)
        )
        prompts = np.zeros((m, sb), np.int32)
        row_lens = np.ones(m, np.int32)  # pad rows: last_idx 0 stays valid
        # pad rows: max_slots is ALWAYS out of bounds for the [max_slots,..]
        # engine state -> scatter drop (m itself can be a valid slot now
        # that m may sit below max_slots)
        slots = np.full(m, self.max_slots, np.int32)
        temp = np.zeros(m, np.float32)
        top_k = np.zeros(m, np.int32)
        top_p = np.ones(m, np.float32)
        seeds = np.zeros(m, np.int32)
        first_steps = np.zeros(m, np.int32)
        for i, p in enumerate(preps):
            prompts[i, : p["s"]] = p["ids"]
            row_lens[i] = p["s"]
            slots[i] = p["slot"]
            temp[i] = float(p["samp"].get("temperature", 0.0))
            top_k[i] = int(p["samp"].get("top_k", 0))
            top_p[i] = float(p["samp"].get("top_p", 1.0))
            seeds[i] = int(p["samp"].get("seed", 0))
            first_steps[i] = int(p["samp"].get("resume_step", 0))
        # top_k/top_p always ride as ARRAYS here (0 / 1.0 = off per row):
        # a None variant would mean two compiles per bucket, and the admit
        # program samples once — the chunk scan's per-step sort-skip
        # optimization has nothing to save on a one-shot program
        self._cache, self._tok, firsts = self._admit_many_prog(
            self.server.params, jnp.asarray(prompts), self._cache, self._tok,
            jnp.asarray(row_lens), self.kv.at_many(slots),
            jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(top_p),
            jnp.asarray(seeds), jnp.asarray(first_steps),
        )
        block = {"dev": firsts, "np": None}

        def first_ref(i: int, block=block):
            if block["np"] is None:
                block["np"] = np.asarray(block["dev"])
            return block["np"][i].reshape(1, 1)

        for i, p in enumerate(preps):
            self._finish_admit_host(p, lambda i=i: first_ref(i))

    def _admit_one(self, prep: dict) -> None:
        ids, samp, slot, s = prep["ids"], prep["samp"], prep["slot"], prep["s"]
        # this dispatch is attributable to ONE request: a loop death here
        # counts against its poison-quarantine budget
        self._suspect_fp = prep["fp"]
        self._suspect_rid = prep["ticket"].request_id
        # registry-installed prefix KV (dl/kv_store.py): count and mark the
        # dispatch when this admit decodes from fleet-shared state — the
        # observable proof a fresh pod skipped a shared-prefix prefill
        installed = False
        if prep["hit"] is not None and self.prefix_cache is not None:
            installed = (
                self.prefix_cache.entry_origin(ids[: prep["hit"][0]])
                == "installed"
            )
            if installed:
                self.stats["prefix_hits_installed"] += 1
        self._rec("dispatch_admit", slot=slot,
                  request_id=prep["ticket"].request_id,
                  prompt_len=s, cached=prep["hit"] is not None,
                  installed_kv=installed)
        hit = prep["hit"]
        where = self.kv.at(slot)
        temp = np.asarray([samp.get("temperature", 0.0)], np.float32)
        k_val = int(samp.get("top_k", 0))
        p_val = float(samp.get("top_p", 1.0))
        filters = k_val > 0 or p_val < 1.0
        top_k = np.asarray([k_val], np.int32) if filters else None
        top_p = np.asarray([p_val], np.float32) if filters else None
        seed = np.asarray([samp.get("seed", 0)], np.int32)
        first_step = np.asarray([samp.get("resume_step", 0)], np.int32)
        if hit is not None:
            plen, stored = hit
            suffix = ids[plen:]
            sb = pad_seq_len(len(suffix))
            block = np.zeros((1, sb), np.int32)
            block[0, : len(suffix)] = suffix
            self._cache, self._tok, first, small = self._admit_cached_prog(
                self.server.params, jnp.asarray(block), self._cache, self._tok,
                jnp.asarray([len(suffix)], np.int32), jnp.int32(plen),
                where, stored, temp, top_k, top_p, seed,
                pad_seq_len(s), first_step,
            )
        else:
            pad_s = pad_seq_len(s)
            prompt = np.zeros((1, pad_s), np.int32)
            prompt[0, :s] = ids
            admitted = self._admit_prog(
                self.server.params, jnp.asarray(prompt), self._cache, self._tok,
                jnp.asarray([s], np.int32), where, temp, top_k, top_p,
                seed, first_step,
            )
            if self.prefix_cache is None:
                self._cache, self._tok, first = admitted
                small = None
            else:
                self._cache, self._tok, first, small = admitted
        if self.prefix_cache is not None:
            # the scratch cache IS this prompt's prefill KV (bucketed to the
            # prompt's 16-quantum): store it so the conversation's next turn
            # prefills only its new suffix
            self.prefix_cache.put(ids, small)
        self._finish_admit_host(
            prep, lambda first=first: np.asarray(first).reshape(1, 1)
        )
        self._suspect_fp = None
        self._suspect_rid = ""

    # -- chunked prefill scheduling -------------------------------------------

    def _start_fill(self, prep: dict) -> None:
        """Begin a chunked prefill on a claimed slot. A prefix hit seeds
        the slot with the stored KV (one insert program) so only the
        suffix lands piece by piece; everything else is host bookkeeping
        — the pieces themselves dispatch from the boundary scheduler."""
        slot, ids = prep["slot"], prep["ids"]
        plen = 0
        if prep["hit"] is not None:
            plen_real, stored = prep["hit"]
            # the fill frontier starts at the stored prefix ROUNDED DOWN
            # to the bucket quantum: every piece then lands 16-aligned,
            # so no piece's bucket can spill past pad16(s) (an unaligned
            # last piece near max_len would make its dynamic_update_slice
            # clamp the write window back over live KV). The <= 15 tokens
            # between the aligned frontier and the real prefix simply
            # re-prefill as part of the first suffix piece, overwriting
            # the seeded bucket's junk span on the way.
            plen = plen_real // SEQ_BUCKET * SEQ_BUCKET
            if plen == 0:
                pass  # sub-bucket prefix: seeding buys nothing
            elif not self.kv.reserve(slot, pad_seq_len(plen_real)):
                # a concurrent preparation raced the seed's room away:
                # fall back to filling the whole prompt incrementally
                plen = 0
            else:
                with trace.span("continuous.fill_seed", prefix=plen):
                    self._cache = self._seed_prog(
                        self._cache, stored, self.kv.at(slot)
                    )
        fill = _Fill(slot, list(ids), prep["n"], dict(prep["samp"]),
                     prep["ticket"], filled=plen, fp=prep.get("fp"))
        # the fill's offset is its KV frontier: decode chunks run over
        # every slot, so this keeps the slot's garbage writes beyond the
        # real prefix (the next piece overwrites them)
        self._offsets[slot] = plen
        self._steps[slot] = 0
        self._filling[slot] = fill
        self._fill_order.append(slot)
        prep["finished"] = True
        self._steady = False  # a fill started: not a steady-decode boundary

    def _fill_piece(self, rem: int) -> tuple[int, int, bool]:
        """(bucketed piece length, real tokens taken, is-last) for a fill
        with ``rem`` prompt tokens outstanding."""
        if rem <= self.prefill_chunk:
            return pad_seq_len(rem), rem, True
        return self.prefill_chunk, self.prefill_chunk, False

    def _dispatch_pieces(self, decode_spend: int) -> bool:
        """Land this boundary's prefill pieces: FIFO over filling rows,
        one piece each, packed into the boundary budget after the decode
        rows' spend. The head piece is exempt from the budget — a budget
        smaller than the decode spend must bound prefill work per
        boundary, not starve fills outright. Returns True when at least
        one piece landed (False = every fill is page-blocked)."""
        spent = decode_spend
        landed = 0
        for slot in list(self._fill_order):
            fill = self._filling.get(slot)
            if fill is None:
                continue
            if fill.ticket.cancelled:
                # retire NOW, not at the next sweep: a cancelled lone
                # fill skipped here would read as "every fill is
                # page-blocked" and trip the preempt wedge check
                self._drop_fill(slot)
                continue
            rem = len(fill.ids) - fill.filled
            piece_len, take, last = self._fill_piece(rem)
            if (landed and self.prefill_budget > 0
                    and spent + piece_len > self.prefill_budget):
                break  # budget spent: later fills wait for the next boundary
            # the last piece also reserves the decode span — the flip must
            # never strand a row that cannot decode
            upto = (self._span(fill.ids, fill.n) if last
                    else fill.filled + piece_len)
            if not self.kv.reserve(slot, upto):
                self.stats["fill_waits"] += 1
                continue
            self._land_piece(fill, piece_len, take, last)
            spent += piece_len
            landed += 1
        return landed > 0

    def _land_piece(self, fill: _Fill, piece_len: int, take: int,
                    last: bool) -> None:
        """Dispatch one prefill piece (async). The last piece samples the
        row's first token and flips the slot from filling to decoding."""
        slot = fill.slot
        # piece dispatches are attributable to the filling request (poison
        # quarantine): a prompt that crashes the loop mid-fill must not be
        # re-admitted forever
        self._suspect_fp = fill.fp
        self._suspect_rid = fill.ticket.request_id
        self._steady = False  # a fill boundary, not steady decode
        if last:
            self._tok_host = None  # the flip program advances the device tok
        block = np.zeros((1, piece_len), np.int32)
        block[0, :take] = fill.ids[fill.filled: fill.filled + take]
        piece = jnp.asarray(block)
        offset = jnp.int32(fill.filled)
        where = self.kv.at(slot, fill.filled, piece_len)
        self.stats["prefill_pieces"] += 1
        self.stats["fill"]["pieces"] += 1
        self.stats["fill"]["tokens"] += take
        fill.ticket.prefill_pieces += 1
        self._rec("fill_piece", slot=slot, request_id=fill.ticket.request_id,
                  tokens=take, last=last)
        if not last:
            # the fill's spans run on the ENGINE thread where the
            # transport's request context isn't set: re-bind the ticket's
            # id so the piece timeline joins the request's trace
            with trace.request_context(fill.ticket.request_id), \
                    trace.span("continuous.prefill_piece", tokens=take):
                self._cache = self._piece_prog(
                    self.server.params, piece, self._cache, offset, where,
                )
            fill.filled += take
            self._offsets[slot] = fill.filled
            self._suspect_fp = None
            self._suspect_rid = ""
            return
        samp = fill.samp
        # filters ride as arrays (0 / 1.0 = off): a one-shot program has
        # no per-step sort to save, same rationale as the batched admit
        temp = np.asarray([samp.get("temperature", 0.0)], np.float32)
        top_k = np.asarray([samp.get("top_k", 0)], np.int32)
        top_p = np.asarray([samp.get("top_p", 1.0)], np.float32)
        seed = np.asarray([samp.get("seed", 0)], np.int32)
        first_step = np.asarray([samp.get("resume_step", 0)], np.int32)
        last_idx = jnp.asarray([take - 1], jnp.int32)
        with trace.request_context(fill.ticket.request_id), \
                trace.span("continuous.prefill_flip", tokens=take):
            self._cache, self._tok, first = self._piece_flip_prog(
                self.server.params, piece, self._cache, self._tok,
                offset, where, last_idx, temp, top_k, top_p, seed, first_step,
            )
        del self._filling[slot]
        self._fill_order.remove(slot)
        if self.prefix_cache is not None:
            # store the freshly landed prompt KV so the conversation's
            # next turn prefills only its new suffix — parity with the
            # single-program admission paths
            self.prefix_cache.put(fill.ids, self._snap_prog(
                self._cache, where, pad_seq_len(len(fill.ids))))
        prep = {"slot": slot, "s": len(fill.ids), "samp": fill.samp,
                "n": fill.n, "ticket": fill.ticket, "ids": fill.ids,
                "finished": False}
        self._finish_admit_host(
            prep, lambda first=first: np.asarray(first).reshape(1, 1)
        )
        self._suspect_fp = None
        self._suspect_rid = ""
        self._requeue_preempted()

    def _requeue_preempted(self) -> None:
        """A fill flipped or died: parked preempted fills may now restart
        (FIFO, ahead of newer arrivals)."""
        if self._preempted:
            self._waiting[:0] = self._preempted
            self._preempted.clear()

    def _drop_fill(self, slot: int, err: BaseException | None = None) -> None:
        """Retire a filling row early: end its stream (_DONE for a gone
        consumer, ``err`` for a deadline expiry) and free the slot and
        pages; nothing was emitted, so nothing else unwinds. The single
        early-fill-retirement path — the sweep, the piece scheduler, the
        preempt guard, and deadline expiry all route here so the
        semantics can't diverge."""
        fill = self._filling.pop(slot, None)
        if fill is not None:
            fill.ticket.out.put(_DONE if err is None else err)
        if slot in self._fill_order:
            self._fill_order.remove(slot)
        self._release_slot(slot)
        self._requeue_preempted()

    def _preempt_fill(self) -> None:
        """Every fill is page-blocked and no decode row is left to free
        pages by retiring: restart the YOUNGEST fill (it has emitted
        nothing, so a restart is exact) — its pages unblock the older
        fills. Parked, not re-queued: an immediate re-admission would
        re-grab the very pages the head fill needs (livelock)."""
        dropped = False
        for slot, fill in list(self._filling.items()):
            if fill.ticket.cancelled:
                # a disconnect racing this boundary (cancel() runs on the
                # consumer's thread) is a retirement, not pool pressure
                self._drop_fill(slot)
                dropped = True
        if dropped or not self._filling:
            return  # freed slots/pages; the next boundary progresses
        if len(self._filling) < 2:
            # cannot happen: the pool holds any single validated row's
            # whole span, so a lone fill always has its remaining pages
            raise RuntimeError(
                "page pool wedged: a lone filling row cannot reserve its "
                "next piece (pool smaller than a validated request?)"
            )
        slot = self._fill_order[-1]
        fill = self._filling.pop(slot)
        self._fill_order.remove(slot)
        self._release_slot(slot)
        self.stats["fill_preempts"] += 1
        self._rec("preempt", slot=slot, request_id=fill.ticket.request_id,
                  filled=fill.filled)
        fill.ticket.restart = True  # head-of-backlog pin: see _Ticket
        fill.ticket.preempts += 1
        self._preempted.append((fill.ids, fill.n, fill.samp, fill.ticket))
        self._backlog_add(1)  # back in the not-yet-admitted set

    def _overlap_prep(self) -> None:
        """Boundary-prep overlap: called while dispatched programs are
        executing, BEFORE the loop blocks on the oldest result. Drains the
        submit queue into the FIFO backlog (same arrival order the main
        pop preserves) and pre-computes the expensive host-side admission
        prep — the poison fingerprint (an O(prompt) hash) and the
        prefix-cache lookup — for the backlog's head, so the next
        admission boundary swaps prepared inputs and dispatches instead of
        doing that work serially between device programs. A lookup
        memoized here can go stale against a store that lands afterwards;
        that misses an optimization, never correctness (the admission
        paths are exact with or without a hit)."""
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is None:
                # the close sentinel is strictly last (close() enqueues it
                # under the same lock submits take): hand it back for the
                # main pop's close path
                self._q.put(None)
                break
            if isinstance(item, list):
                for row_item in item:
                    self._backlog_insert(row_item)
            else:
                self._backlog_insert(item)
        # only the head can admit next boundary; +2 covers slots that the
        # in-flight programs' plans just freed
        limit = len(self._free) + 2
        for item in self._waiting[:limit]:
            ticket = item[3]
            if ticket.cancelled or ticket in self._prep_memo:
                continue
            fp = _fingerprint(item[0], item[1])
            hit = None
            if self.prefix_cache is not None:
                hit = self.prefix_cache.lookup(item[0], max_total=self.max_len)
            self._prep_memo[ticket] = (fp, hit)

    def _pick_depth(self) -> int:
        """Chunks per device program for THIS dispatch. Depth > 1 only in
        steady decode: any pending boundary event (a fill piece due, a
        backlog/queue item wanting admission, a first token owed) snaps
        back to per-chunk dispatch so that event isn't delayed by a deep
        program's span. The cap at every row's remaining budget keeps the
        deepest write inside the validated ``_overrun`` span (a row that
        finishes mid-program keeps writing to the program's end, exactly
        like the existing mid-chunk finish — never more than one
        chunk_size past its budget).

        Depth walks a POWER-OF-TWO ladder (1, 2, 4, ... cap), not every
        integer: each distinct depth is a separate compiled ``n_steps``
        variant, and an arbitrary-depth tail (rem 3 chunks -> depth 3,
        rem 2 -> depth 2...) would pay a fresh XLA compile MID-LOAD the
        first time every tail size appears — measured as hundreds of ms
        landing in the steady-decode boundary histogram. The ladder
        bounds the variant count at log2(cap)+1 while keeping the deep
        steady-state program."""
        if self._depth_cap <= 1 or not self._rows:
            return 1
        if (self._filling or self._waiting or self._preempted
                or self._first_pending or not self._q.empty()):
            return 1
        rem_min = min(r.budget - r.emitted for r in self._rows.values())
        fit = min(self._depth_cap, rem_min // self.chunk_size)
        if fit <= 1:
            return 1
        depth = 1
        while depth * 2 <= fit:
            depth *= 2
        return depth

    def _dispatch_chunk(self) -> tuple:
        """Dispatch one decode program (async) and PLAN its emissions now.
        Take counts and retirements are value-independent (budgets only),
        so scheduling runs a full program ahead of token delivery — the
        host's dispatch round-trip overlaps the device decoding the chunks in flight instead of serializing
        with it. In steady decode the program scans ``depth`` chunks
        (_pick_depth), amortizing the fixed dispatch cost, and the token
        block's device->host copy STARTS here so the lagged readback in
        ``_deliver`` finds the bytes already on their way."""
        depth = self._pick_depth()
        n_steps = depth * self.chunk_size
        active = list(self._rows)
        # a request the engine holds that wants a slot (what _pick_depth
        # asks): a vacant slot's steps are then the engine's to mend, else
        # the clients' turnaround
        queued = bool(self._waiting or self._preempted or not self._q.empty())
        filtered = bool(self._use_filters[active].any())
        self._rec("dispatch", depth=depth, n_steps=n_steps,
                  active=len(self._rows), devices=self.mesh_devices)
        self._cache, self._tok, toks_dev = self._chunk(
            self.server.params, self._cache, self._tok,
            *self._chunk_args(filtered), n_steps=n_steps,
        )
        # start the device->host token copy NOW: it streams back while the
        # device runs the next program, so the lagged _deliver sync finds
        # the bytes resident instead of paying the full fetch round-trip
        toks_dev.copy_to_host_async()
        self._tok_host = None  # the in-flight program advances tok
        self.stats["chunks"] += depth
        self.stats["dispatches"] += 1
        # pad accounting: live rows (decoding + filling) vs the program's
        # static max_slots row dimension, weighted by chunk-equivalents
        n_live = len(self._rows) + len(self._filling)
        self.stats["decode_rows"] += self.max_slots * depth
        self.stats["decode_pad_rows"] += (
            max(self.max_slots - n_live, 0) * depth
        )
        self.kv.count_sweep(self._offsets, n_steps)
        self._depth_last = depth
        if depth > self.stats["dispatch_depth_max"]:
            self.stats["dispatch_depth_max"] = depth
        self._inflight_chunks += depth
        if self._inflight_chunks > self.stats["sync_lag_chunks_max"]:
            self.stats["sync_lag_chunks_max"] = self._inflight_chunks
        now = time.monotonic()
        if self._last_chunk_t is not None:
            # decode-boundary cadence: the max gap between consecutive
            # chunk dispatches while rows were active IS the admission
            # stall a decoding client can observe (monolithic prefills
            # used to sit here for the whole prompt)
            gap_ms = (now - self._last_chunk_t) * 1e3
            if gap_ms > self.stats["stall_ms_max"]:
                self.stats["stall_ms_max"] = round(gap_ms, 3)
            # the boundary's HOST cost: the dispatch-to-dispatch gap minus
            # the time spent blocked on device results — what the pipelined
            # scheduler is supposed to keep off the critical path
            host_ms = max(0.0, gap_ms - self._sync_wait_s * 1e3)
            self._boundary_host_ms.append(host_ms)
            if (self._steady
                    and self._boundary_syncs
                    > self.stats["host_syncs_per_boundary"]):
                # steady decode must cost at most ONE blocking sync per
                # boundary (the lagged token readback) — tests assert this
                self.stats["host_syncs_per_boundary"] = self._boundary_syncs
        self._sync_wait_s = 0.0
        self._boundary_syncs = 0
        self._steady = True
        self._last_chunk_t = now
        self._offsets += n_steps
        self._steps += n_steps
        # an idle slot decodes garbage at offset 0 onwards in every program:
        # left to drift, its context, and what attention reads for it, grows
        self._offsets[self._free] = 0
        for slot, fill in self._filling.items():
            # filling slots don't decode: their offsets stay pinned at the
            # fill frontier (the chunk's garbage writes land beyond it and
            # the next piece overwrites them)
            self._offsets[slot] = fill.filled
            self._steps[slot] = 0
        plan = []
        taken = 0
        for slot, row in list(self._rows.items()):
            # the chunk's final carry is this row's next (undelivered)
            # token — the spec step must emit it before verifying onward
            row.tok_pending = True
            take = min(n_steps - row.skip, row.budget - row.emitted)
            row.emitted += max(take, 0)
            taken += max(take, 0)
            done = row.emitted >= row.budget
            plan.append((slot, row, row.skip, take, done))
            row.skip = 0
            if done:  # slot reuse is safe: a re-admission's cache insert is
                # data-ordered after the in-flight chunk's writes
                del self._rows[slot]
                self._release_slot(slot)  # idle rows write harmlessly at 0
        self._tokens_in_flight += taken
        if self._tokens_in_flight > self.stats["tokens_in_flight_peak"]:
            self.stats["tokens_in_flight_peak"] = self._tokens_in_flight
        # a live row's steps are tokens or edge (its skip, what lies past its
        # budget); a slot with neither row nor fill is vacant
        vacant = (self.max_slots - n_live) * n_steps
        self._count_row_steps(
            tokens=taken, edge=len(active) * n_steps - taken,
            filling=len(self._filling) * n_steps,
            vacant_queued=vacant if queued else 0,
            vacant_idle=0 if queued else vacant,
            total=self.max_slots * n_steps)
        return toks_dev, plan, depth

    def _count_row_steps(self, **more: int) -> None:
        """Add to the row-step ledger. The dict is replaced whole, never
        updated in place: ``snapshot()`` runs on other threads, and what it
        copies must be five counters that sum to ``total``."""
        self.stats["row_steps"] = {
            k: v + more.get(k, 0) for k, v in self.stats["row_steps"].items()}

    def _tokens_to_edge(self, n: int) -> None:
        """``n`` tokens a dispatch planned for a client were not handed over
        (a stop token, a cancel, a dead engine): their steps gave nothing."""
        if n > 0:
            self._count_row_steps(tokens=-n, edge=n)

    def _deliver_firsts(self) -> None:
        """Hand this iteration's admitted rows their prefill tokens. Blocks
        only on the prefills (ordered before any chunk dispatched after
        them), so N admissions pay one round-trip, not N."""
        phases = self._phases
        phases.to(_P_FANOUT)
        firsts, self._first_pending = self._first_pending, []
        for row, first_ref, done in firsts:
            if row.ticket.cancelled:  # consumer gone: free the slot, no put
                row.out.put(_DONE)
                row.closed = True
                continue
            phases.to(_P_FIRSTS_WAIT)
            t0 = time.monotonic()
            first_np = first_ref()
            # device-wait, not host work: keep it out of boundary_host_ms
            self._sync_wait_s += time.monotonic() - t0
            phases.to(_P_FANOUT)
            ticket = row.ticket
            if not ticket.t_first:
                ticket.t_first = time.monotonic()
                # the histograms feed HERE, once per request, from the
                # same stamps the client's timing block reports
                if ticket.t_submit:
                    if ticket.t_admit:
                        self.hist_queue_ms.observe(
                            (ticket.t_admit - ticket.t_submit) * 1e3)
                    self.hist_ttft_ms.observe(
                        (ticket.t_first - ticket.t_submit) * 1e3)
            if row.seq is not None:
                row.seq.append(int(first_np[0, 0]))
            row.out.put(first_np)
            self.rate_tokens.add(1)
            if row.stops and int(first_np[0, 0]) in row.stops and not done:
                row.out.put(_DONE)
                row.closed = True  # plan retires the slot next dispatch
                self._rec("eos", slot=row.slot,
                          request_id=ticket.request_id,
                          reason="stop", emitted=row.emitted)
            elif done:
                row.out.put(_DONE)
                self._rec("eos", slot=row.slot,
                          request_id=ticket.request_id,
                          reason="budget", emitted=row.emitted)

    def _put_pieces(self, row: _Row, arr: np.ndarray) -> None:
        """Hand a row its tokens in flush-cadence pieces: a depth-D
        program's take splits into <= chunk_size slices so streaming
        clients keep the per-chunk flush granularity the serial path had
        (serve.py writes one SSE flush per queue item)."""
        cs = self.chunk_size
        for j in range(0, arr.shape[1], cs):
            row.out.put(arr[:, j:j + cs])

    def _deliver(self, pending: tuple | None) -> None:
        """Block on an in-flight program's tokens and hand them to waiters.
        This is the boundary's ONE lagged device sync: the async copy
        started at dispatch, so in steady pipelined decode this wait is
        the residue the device hasn't streamed back yet, not a full
        round-trip. The block's extra trailing column is the lookahead
        carry (each row's next, undelivered token) — cached host-side for
        the spec-mode transition. Stop hits here lag dispatch by the
        in-flight span; the row just closes and its slot frees at the next
        sweep (its offsets die with the slot — the overrun rewind is the
        slot release, exactly like the speculative path's rejected tail)."""
        if pending is None:
            return
        toks_dev, plan, depth = pending
        self._phases.to(_P_WAIT_TOKENS)
        t0 = time.monotonic()
        toks = np.asarray(toks_dev)
        wait_s = time.monotonic() - t0
        self._phases.to(_P_FANOUT)
        self.kv.landed(toks)  # what the layout sent home below the slots' rows
        self._sync_wait_s += wait_s
        self._boundary_syncs += 1
        self._inflight_chunks = max(0, self._inflight_chunks - depth)
        self._rec("readback", depth=depth, rows=len(plan),
                  wait_ms=round(wait_s * 1e3, 3))
        self.rate_tokens.add(sum(max(take, 0) for _, _, _, take, _ in plan))
        # valid until the next dispatch/admission advances the device tok
        # (the dispatch path resets it to None first)
        self._tok_host = toks[:, -1].copy()
        for slot, row, skip, take, done in plan:
            self._tokens_in_flight = max(0, self._tokens_in_flight - max(take, 0))
            if row.closed:
                self._tokens_to_edge(take)
                continue  # stop token already ended the row (and its queue)
            if row.ticket.cancelled:
                # client disconnected mid-stream: stop piling tokens into a
                # queue nobody drains; the sweep frees the slot next round
                row.out.put(_DONE)
                row.closed = True
                self._tokens_to_edge(take)
                continue
            piece = toks[slot : slot + 1, skip : skip + take] if take > 0 else None
            if piece is not None and row.seq is not None:
                row.seq.extend(piece[0].tolist())
            if piece is not None and row.stops:
                from modelx_tpu.models.decode import stop_cut

                cut = stop_cut(piece[0].tolist(), row.stops)
                if cut is not None:
                    self._put_pieces(row, piece[:, :cut])  # include the stop
                    self._tokens_to_edge(take - cut)
                    row.out.put(_DONE)
                    row.closed = True
                    self._rec("eos", slot=slot,
                              request_id=row.ticket.request_id,
                              reason="stop", emitted=row.emitted)
                    continue
            if piece is not None:
                self._put_pieces(row, piece)
            if done:
                row.out.put(_DONE)
                self._rec("eos", slot=slot, request_id=row.ticket.request_id,
                          reason="budget", emitted=row.emitted)

    @staticmethod
    def _deadline_passed(ticket: _Ticket, now: float) -> bool:
        return (not ticket.cancelled and ticket.deadline is not None
                and now > ticket.deadline)

    def _sweep_backlog(self) -> None:
        """Purge dead backlog entries at EVERY boundary, deadline knob or
        not: cancelled rows (the client is gone — their corpses must not
        occupy --max-queue-depth budget and shed live traffic with 429s)
        end with _DONE, past-deadline rows with the 504 error — both
        without ever taking a slot."""
        now = time.monotonic()
        for lst, state in ((self._waiting, "waiting for a slot"),
                           (self._preempted, "waiting for pages")):
            keep = []
            for item in lst:
                ticket = item[3]
                if ticket.cancelled:
                    self._backlog_sub(1)
                    self._prep_memo.pop(ticket, None)
                    ticket.out.put(_DONE)
                elif self._deadline_passed(ticket, now):
                    self.stats["expired"] += 1
                    self._rec("deadline", request_id=ticket.request_id,
                              state=state)
                    self._backlog_sub(1)
                    self._prep_memo.pop(ticket, None)
                    ticket.out.put(self._deadline_error(ticket, state))
                else:
                    keep.append(item)
            lst[:] = keep

    def _expire_deadlines(self) -> None:
        """Expire past-deadline ADMITTED requests at the chunk boundary:
        filling rows release their slot and pages (nothing was emitted),
        decoding rows fail mid-stream and their slot frees at this sweep.
        Overload turns into fast, observable 504s instead of requests that
        finish long after their caller gave up."""
        now = time.monotonic()
        for slot, fill in list(self._filling.items()):
            if self._deadline_passed(fill.ticket, now):
                self.stats["expired"] += 1
                self._rec("deadline", slot=slot,
                          request_id=fill.ticket.request_id,
                          state="prefilling")
                self._drop_fill(
                    slot, self._deadline_error(fill.ticket, "prefilling")
                )
        for row in self._rows.values():
            if not row.closed and self._deadline_passed(row.ticket, now):
                self.stats["expired"] += 1
                self._rec("deadline", slot=row.slot,
                          request_id=row.ticket.request_id,
                          state="decoding")
                row.out.put(self._deadline_error(row.ticket, "decoding"))
                row.closed = True  # the sweep below frees the slot

    def _sweep_closed(self) -> None:
        """Free the slots of rows a stop token ended at delivery time or a
        client abandoned (ticket.cancelled) — BEFORE admission and the next
        dispatch, so a waiting request takes the slot immediately and no
        dead-row chunk is dispatched."""
        self._sweep_backlog()
        self._expire_deadlines()
        for slot, row in list(self._rows.items()):
            if row.ticket.cancelled and not row.closed:
                row.out.put(_DONE)  # unblock any racing drain
                row.closed = True
            if row.closed:
                del self._rows[slot]
                self._release_slot(slot)
        for slot, fill in list(self._filling.items()):
            if fill.ticket.cancelled:  # consumer gone mid-fill: nothing
                # was emitted, so the slot and pages just free
                self._drop_fill(slot)

    def _run(self) -> None:
        """The engine thread: run the loop, and — supervision — restart it
        after a crash. ``_loop`` itself drains every waiter on death (no
        request ever hangs); this outer loop decides whether the engine
        comes back: exponential crash-loop backoff between restarts, and a
        circuit breaker (``max_crashes`` within ``crash_window_s``) that
        leaves the engine broken when restarting clearly isn't helping."""
        while True:
            verdict = self._loop()
            if verdict != "crashed":
                return
            # backoff grows with the number of recent crashes: one isolated
            # crash restarts almost immediately, a crash loop slows down
            delay = self.restart_backoff_s * (2 ** max(0, len(self._crash_times) - 1))
            self._closed_ev.wait(delay)
            with self._close_lock:
                bail = self._closed
                if bail and self._broken is None:
                    self._broken = EngineBrokenError("closed during restart")
            if bail:
                # requests enqueued during the backoff must not hang
                self._drain_queue(EngineBrokenError("continuous batcher closed"))
                self._state = "stopped"
                return
            self._rebuild()
            with self._close_lock:
                self._restarts += 1
                self.stats["engine_restarts"] = self._restarts
                self._state = "running"
            logging.getLogger("modelx.serve").warning(
                "continuous engine restarted (restart #%d)", self._restarts
            )

    def _watchdog(self) -> None:
        """Hang monitor (``boundary_watchdog_s`` > 0): the supervisor only
        heals CRASHES — a device dispatch that never returns (real on TPU:
        a wedged transfer or a hung collective) would hold the loop, and
        every waiter, forever. This thread watches the loop's per-boundary
        progress stamp; a stall past the window with rows active fails
        every waiter NOW (the ticket queues are thread-safe, and the
        wedged loop is inside a device call, not mutating row state),
        flips the state to "restarting" so /healthz drains, and leaves a
        pending error the loop raises the moment the dispatch returns —
        the stall then feeds the ordinary crash/restart/breaker path. A
        second put from that path is harmless: consumers stop at their
        first error item. The poll is window/4 but capped at 250ms — the
        check is a handful of attribute reads, and a short cadence keeps
        detection prompt even under a large warm-up-safe window (or one
        an operator tightens on a live engine once compiles clear)."""
        while not self._closed_ev.wait(
                max(0.01, min(0.25, self.boundary_watchdog_s / 4))):
            if self._watch_stall is not None or self._state != "running":
                continue
            last = self._progress_t
            busy = bool(self._rows or self._filling or self._first_pending)
            if not busy or last is None:
                continue
            stalled_s = time.monotonic() - last
            if stalled_s <= self.boundary_watchdog_s:
                continue
            err = EngineBrokenError(
                f"boundary watchdog: no dispatch progress in "
                f"{stalled_s:.2f}s (window {self.boundary_watchdog_s}s)"
            )
            self._watch_stall = err
            self.stats["watchdog_stalls"] += 1
            self._rec("watchdog_stall", stalled_s=round(stalled_s, 3),
                      window_s=self.boundary_watchdog_s)
            self._state = "restarting"  # readiness drains while wedged
            # the wedged loop cannot dump for itself (it is inside a device
            # call): the watchdog writes the black box NOW, while the
            # evidence — ring + per-slot state — still describes the stall
            self._flight_dump("watchdog", err)
            logging.getLogger("modelx.serve").error(
                "continuous engine stalled: no boundary progress in %.2fs "
                "(watchdog %.2fs) — failing %d active row(s)",
                stalled_s, self.boundary_watchdog_s,
                len(self._rows) + len(self._filling),
            )
            for row in list(self._rows.values()):
                row.out.put(err)
            for fill in list(self._filling.values()):
                fill.ticket.out.put(err)

    def _rebuild(self) -> None:
        """Fresh engine state after a crash: new KV cache (or page pool),
        zeroed host vectors, every slot free. The compiled programs are
        pure functions of their inputs and are REUSED — restart cost is one
        cache allocation, not a recompile. The prefix cache is preserved:
        its entries are keyed by token prefix and independent of slot
        state, so multi-turn conversations keep their fast path across a
        restart."""
        self.kv.reset()
        self.allocate_device_state()
        self._offsets[:] = 0
        self._steps[:] = 0
        self._temp[:] = 0.0
        self._top_k[:] = 0
        self._top_p[:] = 1.0
        self._seeds[:] = 0
        self._use_filters[:] = False
        self._rows = {}
        self._free = list(range(self.max_slots))
        self._first_pending = []
        self._filling = {}
        self._fill_order = []
        self._preempted = []
        self._suspect_fp = None
        self._suspect_rid = ""
        self._last_chunk_t = None
        self._prep_memo = {}
        self._tok_host = None
        self._watch_stall = None
        self._progress_t = None
        if self.flightrec is not None:
            # fresh flight: the rebuilt engine must not replay the dead
            # engine's timeline into its next black box
            self.flightrec.reset()
            self._rec("rebuild", restarts=self._restarts + 1)
        self._sync_wait_s = 0.0
        self._boundary_syncs = 0
        self._steady = False
        self._tokens_in_flight = 0
        self._inflight_chunks = 0
        self._depth_last = 1

    def _loop(self) -> str:
        from collections import deque

        pending: "deque[tuple]" = deque()  # in-flight chunks, oldest first
        phases = self._phases
        try:
            while True:
                # one step per iteration; the leaf phases below tile it
                phases.begin(_P_SWEEP, self.stats["dispatches"])
                if self._watch_stall is not None:
                    # the watchdog declared this boundary stalled while a
                    # dispatch was wedged; it already failed the waiters —
                    # unwind into the supervisor so the state rebuilds
                    raise self._watch_stall
                self._progress_t = time.monotonic()
                self._sweep_closed()
                if not self._rows:
                    # idle (or fill-only) gaps between chunks aren't
                    # decode stalls — don't let them pollute stall_ms_max
                    # (or the boundary host-time histogram)
                    self._last_chunk_t = None
                    self._sync_wait_s = 0.0
                    self._boundary_syncs = 0
                # gather everything admissible (up to free slots), FIFO: the
                # backlog of earlier arrivals that found no slot goes first.
                # Preparation claims the slot/pages immediately so the
                # admissibility check for the NEXT item sees true capacity;
                # the device dispatches happen together below so same-bucket
                # bursts share one program. Block on the queue only when
                # fully idle with nothing in flight AND no admitted row
                # still owed its (async) first token — a lone budget-1
                # request admits, frees its slot, and would otherwise hang
                # its waiter by blocking here before _deliver_firsts runs
                to_admit: list = []
                phases.to(_P_ADMIT_PREP)
                while True:
                    if self._waiting:
                        if not self._admits_now(self._waiting[0]):
                            break  # still contended: decode on, retry later
                        self._gather_prep(self._waiting.pop(0), to_admit)
                        continue
                    block = (not self._rows and not self._filling
                             and not pending
                             and not self._first_pending and not to_admit)
                    if block:
                        phases.to(_P_IDLE)  # until a request arrives
                    try:
                        item = self._q.get(block=block)
                    except queue.Empty:
                        break
                    if (block and self.burst_window_ms > 0
                            and item is not None and self.max_slots > 1):
                        # the engine was fully idle and one request just
                        # arrived: wait a beat for its co-arrivals so a
                        # burst admits as ONE program and decodes in step
                        # (independent clients racing this loop otherwise
                        # split across admission boundaries — each straggler
                        # group then costs whole extra chunks). A lone
                        # request pays ~1 ms against a ~50+ ms admission
                        # dispatch; requests landing mid-decode never wait.
                        # Applies to submit_many lists too: a single-row
                        # generate IS a 1-row list, and independent clients'
                        # lists co-arrive exactly like tuples do.
                        time.sleep(self.burst_window_ms / 1e3)
                    phases.to(_P_ADMIT_PREP)
                    if isinstance(item, list):
                        # a submit_many burst: route through the FIFO backlog
                        # so the whole burst hits ONE admission boundary
                        # (and shares an admit program) regardless of how
                        # fast this loop drains the queue
                        for row_item in item:
                            self._backlog_insert(row_item)
                        continue
                    if item is None:
                        err = RuntimeError("continuous batcher closed")
                        for prep in to_admit:  # claimed a slot, never decoded
                            prep["ticket"].out.put(err)
                        self._deliver_firsts()
                        while pending:
                            # deliver-then-pop: a chunk that raises stays in
                            # the deque so the except-path failsafe fails its
                            # plan rows (they may already be out of _rows)
                            self._deliver(pending[0])
                            pending.popleft()
                        self._fail_active(err)
                        self._state = "stopped"
                        phases.end()
                        return "closed"
                    if not self._admits_now(item):
                        # no slot (or no room in the KV layout): hold in
                        # the FIFO backlog and decode on — a retire this
                        # chunk frees capacity for it
                        self._backlog_insert(item)
                        break
                    self._gather_prep(item, to_admit)
                if to_admit:
                    phases.to(_P_ADMIT_DISPATCH)
                    self._admit_all(to_admit)
                if self._spec_ok():
                    # single greedy row: switch to speculative verify steps
                    # (fewer device steps per token beats pipeline depth
                    # when there is nothing to pipeline WITH). Drain all
                    # in-flight chunks + first tokens so the row's history
                    # is complete, then run one verify round.
                    self._deliver_firsts()
                    while pending:
                        self._deliver(pending[0])  # deliver-then-pop: see above
                        pending.popleft()
                    phases.to(_P_SWEEP)
                    self._sweep_closed()  # a stop may just have closed it
                    if self._spec_ok():
                        phases.to(_P_SPEC)
                        self._spec_step()
                    continue
                n_decode = len(self._rows)
                if self._rows:
                    # keep up to pipeline_depth chunks in flight: plans are
                    # value-independent, so deeper dispatch is exact, and the
                    # oldest chunk's fetch below overlaps the younger chunks'
                    # device time. Go deep only when nothing is waiting for
                    # a slot, nothing new sits in the queue, and no fill
                    # wants its piece interleaved at every boundary.
                    phases.to(_P_CHUNK_DISPATCH)
                    pending.append(self._dispatch_chunk())
                    while (len(pending) < self.pipeline_depth and self._rows
                           and not self._filling
                           and not self._waiting and self._q.empty()):
                        pending.append(self._dispatch_chunk())
                if self._filling:
                    # prefill pieces ride the boundary AFTER the decode
                    # chunk: decode rows spend first, pieces pack into the
                    # budget's remainder — a long admission can no longer
                    # freeze the running batch for its whole prompt
                    phases.to(_P_PIECES)
                    landed = self._dispatch_pieces(n_decode * self.chunk_size)
                    if (not landed and self._filling and not self._rows
                            and not pending and not self._first_pending):
                        # every fill is page-blocked and nothing is left
                        # to retire: restart the youngest to break the tie
                        self._preempt_fill()
                # deliveries overlap the chunks just dispatched.
                # Deliver-then-pop: a chunk whose fetch raises must stay in
                # the deque so _deliver_failsafe fails its plan rows (plan
                # retirees are already out of _rows and _fail_active's reach)
                self._deliver_firsts()
                if pending:
                    # the dispatched programs are executing: do the NEXT
                    # admissions' host prep now (queue drain, fingerprint,
                    # prefix lookup), THEN block on the oldest result —
                    # boundary prep rides inside device time
                    phases.to(_P_OVERLAP_PREP)
                    self._overlap_prep()
                    self._deliver(pending[0])
                    pending.popleft()
        except BaseException as e:  # engine death must not hang waiters
            phases.end()
            logging.getLogger("modelx.serve").exception(
                "continuous engine loop died"
            )
            now = time.monotonic()
            err = (
                e if isinstance(e, ServingError)
                else EngineBrokenError(f"engine loop died: {e!r}")
            )
            if err is not e:
                err.__cause__ = e
            with self._close_lock:
                # circuit breaker: crashes inside the window beyond the
                # budget mean restarting isn't helping — stay broken so
                # /healthz flips and the orchestrator replaces the pod.
                # Decided (and _broken published) under the SAME lock
                # submit checks, so no request can slip into the queue
                # after the broken drain below and hang forever.
                self._crash_times = [
                    t for t in self._crash_times if now - t < self.crash_window_s
                ]
                self._crash_times.append(now)
                broken = (
                    not self.supervise
                    or self._closed
                    or len(self._crash_times) > self.max_crashes
                )
                if broken:
                    self._broken = err
                    self._state = "broken"
                else:
                    self._state = "restarting"
            if self._suspect_fp is not None:
                # the death happened while dispatching ONE request's
                # admission/fill work: charge its quarantine budget
                self._poison[self._suspect_fp] = (
                    self._poison.get(self._suspect_fp, 0) + 1
                )
                self._suspect_fp = None
            self._rec("crash", request_id=self._suspect_rid,
                      error=repr(e)[:200],
                      verdict="broken" if broken else "crashed")
            if e is not self._watch_stall:
                # a watchdog stall already dumped mid-wedge, with the
                # pre-unwind slot state; don't overwrite that evidence
                self._flight_dump("circuit-break" if broken else "crash", err)
            self._suspect_rid = ""
            self._deliver_failsafe(pending, err)
            self._fail_active(err, drain_queue=broken)
            return "broken" if broken else "crashed"

    def _deliver_failsafe(self, pending, err: BaseException) -> None:
        """On engine death, rows in an undelivered plan (or with undelivered
        prefill tokens) were possibly already removed from _rows — fail them
        directly so their waiters don't hang."""
        for row, _first, _done in self._first_pending:
            row.out.put(err)
        self._first_pending = []
        for _toks_dev, plan, _depth in pending:
            for _slot, row, _skip, take, _done in plan:
                row.out.put(err)
                self._tokens_to_edge(take)
        self._tokens_in_flight = 0
        self._inflight_chunks = 0

    @staticmethod
    def _is_batch(item) -> bool:
        samp = item[2]
        return isinstance(samp, dict) and samp.get("priority") == "batch"

    def _backlog_insert(self, item) -> None:
        """Priority-aware FIFO: an interactive item queues ahead of the
        TRAILING run of batch items, FIFO within each class — when the
        backlog is mixed, the boundary scheduler admits interactive work
        first (the router's shed-batch-first contract, continued inside
        the engine). Two bounds on the cut-in: a restart-pinned ticket
        (a preempted fill spliced at the head — its exact restart must
        stay ahead of newer arrivals) is never crossed, and the backward
        scan touches only the trailing batch run, so with no batch work
        queued (the universal case) this IS a plain O(1) append."""
        if not self._is_batch(item):
            i = len(self._waiting)
            while i > 0:
                queued = self._waiting[i - 1]
                if not self._is_batch(queued) or queued[3].restart:
                    break
                i -= 1
            if i < len(self._waiting):
                self._waiting.insert(i, item)
                return
        self._waiting.append(item)

    def _backlog_add(self, n: int) -> None:
        with self._close_lock:
            self._backlog += n

    def _backlog_sub(self, n: int) -> None:
        with self._close_lock:
            self._backlog = max(0, self._backlog - n)

    def _drain_queue(self, err: BaseException) -> None:
        """Fail every row still sitting in the submit queue (crash, close,
        or closed-during-restart paths)."""
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                return
            if item is None:
                continue
            rows = item if isinstance(item, list) else [item]
            self._backlog_sub(len(rows))
            for row_item in rows:
                row_item[3].out.put(err)

    def _fail_active(self, err: BaseException, drain_queue: bool = True) -> None:
        for row in self._rows.values():
            row.out.put(err)
        self._rows.clear()
        for fill in self._filling.values():  # mid-fill rows have waiters
            fill.ticket.out.put(err)
        self._filling.clear()
        self._fill_order.clear()
        for item in self._preempted:  # parked fills too
            item[3].out.put(err)
        self._backlog_sub(len(self._preempted))
        self._preempted.clear()
        for item in self._waiting:  # FIFO backlog items have waiters too
            item[3].out.put(err)
        self._backlog_sub(len(self._waiting))
        self._waiting.clear()
        self._prep_memo.clear()  # memoized prep died with its backlog
        if drain_queue:
            # broken/close: nothing will ever serve the queue — fail it.
            # A supervised restart SKIPS this: queued rows were never
            # touched by the engine, so they survive intact and admit
            # normally once the rebuilt loop comes back up.
            self._drain_queue(err)

    # -- public API -----------------------------------------------------------

    def snapshot(self) -> dict:
        """Counters + live gauges for the metrics endpoint and bench:
        cumulative stats (chunks/admitted/active_peak, prefill_pieces,
        stall_ms_max, spec_* when speculating, pages_* when paged) plus
        the instantaneous active/filling/waiting row counts — operators
        and the bench read THIS, not engine internals."""
        # a block of counters (row_steps, the layout's moe / ssm / ...) is
        # copied too: what is handed out must not grow under its reader
        snap = {k: dict(v) if isinstance(v, dict) else v
                for k, v in self.stats.items()}
        snap["active"] = len(self._rows)
        snap["filling"] = len(self._filling)
        snap["waiting"] = len(self._waiting) + len(self._preempted)
        # pipelined-dispatch surface: the effective depth of the last
        # program, instantaneous in-flight gauges, and the per-boundary
        # host-overhead histogram (dispatch-to-dispatch gap minus the
        # blocking token-fetch wait) — the observable the ISSUE 7 win is
        # measured by
        snap["dispatch_depth"] = self._depth_last
        # the engine thread's time by phase (cumulative seconds / entries),
        # its wall time and its own CPU time: over the phases that do not
        # wait, wall minus CPU is time spent standing by for the GIL
        phases = self._phases
        snap["phase_s"] = {n: round(v, 6) for n, v in zip(phases.names, phases.seconds)}
        snap["phase_n"] = dict(zip(phases.names, phases.entries))
        snap["loop_wall_s"] = round(phases.wall_s, 6)
        snap["loop_cpu_s"] = round(phases.cpu_s, 6)
        snap["tokens_in_flight"] = self._tokens_in_flight
        snap["sync_lag_chunks"] = self._inflight_chunks
        # snapshot() runs on HTTP handler threads while the engine loop
        # appends: list(deque) is one C-level copy (atomic under the GIL,
        # no Python re-entry for float elements); the retry covers any
        # interpreter where a concurrent append still surfaces as the
        # "deque mutated during iteration" RuntimeError
        try:
            hist_list = list(self._boundary_host_ms)
        except RuntimeError:
            hist_list = list(self._boundary_host_ms)
        if hist_list:
            hist = np.asarray(hist_list, np.float64)
            snap["boundary_host_ms_p50"] = round(float(np.percentile(hist, 50)), 3)
            snap["boundary_host_ms_p99"] = round(float(np.percentile(hist, 99)), 3)
            snap["boundary_host_ms_count"] = int(hist.size)
        # padding tax (ISSUE 17): fraction of dispatched decode row-chunks
        # that carried no live request, plus — paged in-place mode — how
        # much of the static page-table width the ragged sweep actually
        # walked (1.0 would mean the pow2 bucket was always full)
        if self.stats.get("decode_rows"):
            snap["pad_fraction"] = round(
                self.stats["decode_pad_rows"] / self.stats["decode_rows"], 4
            )
        if self.stats.get("pages_swept_possible"):
            snap["pages_swept_fraction"] = round(
                self.stats["pages_swept"]
                / self.stats["pages_swept_possible"], 4
            )
        # per-request latency histograms (ISSUE 13): present once a first
        # token delivered — the gate mirrors boundary_host_ms_*, so an
        # idle engine's snapshot keeps its pre-PR shape
        qh = self.hist_queue_ms.snapshot()
        if qh["count"]:
            snap["queue_ms_hist"] = qh
        th = self.hist_ttft_ms.snapshot()
        if th["count"]:
            snap["ttft_ms_hist"] = th
        # supervision + bounded-admission surface: the operator's view of
        # the self-healing layer (engine_restarts rides in from stats)
        snap["engine_state"] = self._state
        # serving topology: the mesh the engine's programs compiled under
        # and the device count its chunk work spreads over — the labels a
        # fleet dashboard joins per-device throughput against
        from modelx_tpu.parallel.mesh import mesh_str

        snap["mesh"] = mesh_str(self.mesh)
        snap["mesh_devices"] = self.mesh_devices
        snap["quarantined"] = sum(
            1 for c in self._poison.values() if c >= self.POISON_CRASHES
        )
        snap["queue_depth"] = self._backlog
        if self.max_queue_depth > 0:
            snap["max_queue_depth"] = self.max_queue_depth
        if self.request_timeout_s > 0:
            snap["request_timeout_s"] = self.request_timeout_s
        # windowed rates (ISSUE 15): recent-rate truth without a scraper —
        # tokens delivered per second over the 1m/5m trailing windows
        snap["tokens_per_s_1m"] = round(self.rate_tokens.rate(60), 4)
        snap["tokens_per_s_5m"] = round(self.rate_tokens.rate(300), 4)
        if self.flightrec is not None:
            snap["flightrec_events"] = self.flightrec.total
        if self.device_telemetry:
            # measured device occupancy (utils/devmem): accountant truth
            # (or the live-buffer census on backends without one) next to
            # the engine's own estimates; `source` says which it was
            dm = devmem.sample()
            snap["hbm_bytes_in_use"] = dm["hbm_bytes_in_use"]
            snap["hbm_bytes_reservable"] = dm["hbm_bytes_reservable"]
            snap["hbm_source"] = dm["source"]
        return snap

    @property
    def engine_state(self) -> str:
        """running | restarting | broken | stopped — what /healthz reads."""
        return self._state

    def _validate(self, ids: list[int], max_new_tokens: int) -> None:
        s = len(ids)
        if s < 1:
            raise ValueError("empty prompt row")
        # + overrun margin: the slot keeps writing to the end of its last
        # chunk (or speculative verify block) even past the budget; those
        # positions must exist
        need = pad_seq_len(s) + max_new_tokens + self._overrun
        if need > self.max_len:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds the "
                f"engine's max_len {self.max_len} (margin {self._overrun})"
            )
        never = self.kv.never_holds(need)
        if never:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) {never}")

    def _check_quarantine(self, ids, n: int) -> None:
        if not self._poison:
            return  # the universal case: no crash ever attributed — free
        crashes = self._poison.get(_fingerprint(ids, n), 0)
        if crashes >= self.POISON_CRASHES:
            raise PoisonedRequestError(crashes)

    def _enqueue(self, payload, rows: int) -> None:
        with self._close_lock:
            if self._closed:
                raise RuntimeError("continuous batcher closed")
            if self._broken is not None:
                # checked under the SAME lock the dying engine takes before
                # its final queue drain — a put here either precedes the
                # drain (and gets failed by it) or raises
                raise EngineBrokenError(
                    f"continuous batcher is broken: {self._broken}"
                ) from self._broken
            if (self.max_queue_depth > 0
                    and self._backlog + rows > self.max_queue_depth):
                # bounded admission: shed NOW (429 + Retry-After) — the
                # backlog must never grow without bound under overload
                self.stats["shed"] += rows
                raise QueueFullError(
                    self._backlog, self.max_queue_depth,
                    retry_after=1 + self._backlog // max(1, self.max_slots),
                )
            self._backlog += rows
            self._q.put(payload)

    def _stamp_deadline(self, ticket: _Ticket, timeout_s: float | None = None) -> None:
        """Effective budget = min(engine --request-timeout, the caller's
        propagated remainder). A failover hop that re-submits therefore
        never re-grants a fresh full timeout: the engine stops working
        for a caller whose original budget is gone."""
        eff = self.request_timeout_s if self.request_timeout_s > 0 else 0.0
        if timeout_s is not None and timeout_s > 0:
            eff = min(eff, float(timeout_s)) if eff > 0 else float(timeout_s)
        if eff > 0:
            ticket.deadline = time.monotonic() + eff
            ticket.timeout_s = eff

    def _deadline_error(self, ticket: _Ticket, state: str) -> DeadlineExceededError:
        return DeadlineExceededError(
            state, ticket.timeout_s or self.request_timeout_s
        )

    def submit(self, ids: list[int], max_new_tokens: int, samp: dict,
               timeout_s: float | None = None,
               request_id: str = "") -> _Ticket:
        """Enqueue one prompt row; the returned ticket carries the output
        queue and a ``cancel()`` the transport calls when its client goes
        away (the engine then frees the slot at the next chunk boundary).
        ``timeout_s`` clamps the engine deadline to a propagated
        per-request remainder (deadline propagation, ISSUE 9);
        ``request_id`` threads the transport's end-to-end id into the
        ticket so the engine's per-request timeline is joinable with the
        router's and pod's view of the same request (ISSUE 13)."""
        self._validate(ids, max_new_tokens)
        self._check_quarantine(ids, max_new_tokens)
        ticket = _Ticket()
        ticket.request_id = str(request_id or "")
        ticket.resume_step = int(samp.get("resume_step", 0) or 0)
        ticket.t_submit = time.monotonic()
        self._stamp_deadline(ticket, timeout_s)
        self._enqueue((list(ids), int(max_new_tokens), dict(samp), ticket), 1)
        return ticket

    def submit_many(self, rows: list[tuple[list[int], int, dict]],
                    timeout_s: float | None = None) -> list[_Ticket]:
        """Enqueue several rows as ONE burst: the engine admits them at the
        same chunk boundary, so same-bucket rows share an admit program
        deterministically (a loop of ``submit`` calls races the engine
        thread for that grouping). Used by multi-row ``generate``."""
        for ids, n, _samp in rows:
            self._validate(ids, n)
            self._check_quarantine(ids, n)
        tickets = [_Ticket() for _ in rows]
        now = time.monotonic()
        for t, (_ids, _n, samp) in zip(tickets, rows):
            t.t_submit = now
            t.resume_step = int(samp.get("resume_step", 0) or 0)
            self._stamp_deadline(t, timeout_s)
        self._enqueue([
            (list(ids), int(n), dict(samp), t)
            for (ids, n, samp), t in zip(rows, tickets)
        ], len(rows))
        return tickets

    def submit_row(self, ids: list[int], max_new_tokens: int, samp: dict) -> "queue.Queue":
        return self.submit(ids, max_new_tokens, samp).out

    def _drain_row(self, out: "queue.Queue") -> Iterator[np.ndarray]:
        while True:
            item = out.get()
            if item is _DONE:
                return
            if isinstance(item, ServingError):
                # typed failures (engine death, deadline, shed) surface
                # as-is: one exception class = one HTTP mapping, identical
                # between the streaming and non-streaming paths
                raise item
            if isinstance(item, BaseException):
                raise EngineBrokenError(
                    f"continuous decode failed: {item}"
                ) from item
            yield item

    def generate(self, tokens: np.ndarray, max_new_tokens: int = 16,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                 seed: int = 0, stop_token_ids=None,
                 timeout_s: float | None = None,
                 priority: str = "interactive",
                 timing: dict | None = None) -> np.ndarray:
        """[B, S + m], matching ModelServer.generate: rows of a multi-row
        request become independent slots with seeds seed+i (the same
        per-row streams the ragged path derives). With ``stop_token_ids``,
        every row's SLOT frees at its stop (concurrent requests stop
        starving behind rows that already finished); m is the longest
        row's emitted length, shorter rows padded by repeating their stop
        token — the serving layer's inclusive-trim cuts at the FIRST stop,
        so padding is invisible in responses."""
        tokens = np.asarray(tokens, np.int32)
        b, s = tokens.shape
        stops = list(stop_token_ids or ())
        tickets = self.submit_many([
            (tokens[i].tolist(), max_new_tokens,
             {"temperature": temperature, "top_k": top_k, "top_p": top_p,
              "seed": (seed + i) % (2**31), "stop_token_ids": stops,
              "priority": priority})
            for i in range(b)
        ], timeout_s=timeout_s)
        outs = [t.out for t in tickets]
        rows = []
        emitted = 0
        try:
            for out in outs:
                pieces = list(self._drain_row(out))
                row = np.concatenate(pieces, axis=1)
                emitted += int(row.size)
                rows.append(row)
        finally:
            if timing is not None and tickets:
                # a multi-row request reports the WORST row per phase:
                # the client-visible latency is bounded by the slowest
                for t in tickets:
                    for k, v in t.timing().items():
                        timing[k] = max(timing.get(k, 0), v) \
                            if isinstance(v, (int, float)) else v
        width = max(r.shape[1] for r in rows)
        rows = [
            r if r.shape[1] == width else np.pad(
                r, ((0, 0), (0, width - r.shape[1])), constant_values=int(r[0, -1])
            )
            for r in rows
        ]
        gen = np.concatenate(rows, axis=0)
        self.server.stats["tokens_generated"] += emitted
        return np.concatenate([tokens, gen], axis=1)

    def stream(self, tokens: np.ndarray, max_new_tokens: int = 16,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               seed: int = 0, chunk_size: int = 0,
               stop_token_ids=None, timeout_s: float | None = None,
               priority: str = "interactive",
               resume_step: int = 0, request_id: str = "",
               timing: dict | None = None) -> Iterator[np.ndarray]:
        """Single-row streaming: yields [1, k] arrays of new tokens as the
        engine decodes them (k == 1 for the prefill token, then up to the
        ENGINE's chunk size — the per-request chunk_size arg is accepted for
        interface parity and ignored). A stop-token hit ends the stream
        early and frees the slot.

        ``resume_step`` = k > 0 CONTINUES an interrupted stream: the caller
        passes ``tokens`` = original prompt + the k tokens already emitted,
        ``max_new_tokens`` = the ORIGINAL budget minus k, and the original
        ``seed`` — the row re-prefills (chunked prefill and prefix-cache
        seeding apply unchanged) and its first token is sampled at step k
        of the original (seed, step) stream, so the continuation is
        byte-identical to the tokens the interrupted stream would have
        emitted (schedule-invariance, see the module docstring)."""
        tokens = np.asarray(tokens, np.int32)
        if tokens.shape[0] != 1:
            raise ValueError("continuous stream is single-row")
        resume_step = int(resume_step)
        if resume_step < 0:
            raise ValueError("resume_step must be >= 0")
        if resume_step >= tokens.shape[1]:
            # ids = prompt + emitted, so a valid resume always leaves at
            # least the original prompt's first token ahead of the frontier
            raise ValueError(
                f"resume_step {resume_step} >= row length {tokens.shape[1]} "
                "(pass prompt + emitted tokens)"
            )
        samp = {"temperature": temperature, "top_k": top_k, "top_p": top_p,
                "seed": seed, "stop_token_ids": list(stop_token_ids or ()),
                "priority": priority}
        if resume_step:
            samp["resume_step"] = resume_step
        ticket = self.submit(
            tokens[0].tolist(), max_new_tokens, samp, timeout_s=timeout_s,
            request_id=request_id,
        )
        try:
            for piece in self._drain_row(ticket.out):
                self.server.stats["tokens_generated"] += int(piece.size)
                yield piece
        finally:
            # a consumer that stops early (client disconnect closes the
            # generator) cancels the row so its slot frees at the next
            # chunk boundary; after a full drain this is a no-op
            ticket.cancel()
            if timing is not None:
                # the caller's out-param: filled HERE (generator close or
                # exhaustion) so the transport reads a complete breakdown
                # exactly when the stream ends
                timing.update(ticket.timing())

    def close(self) -> None:
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
            self._q.put(None)
        self._closed_ev.set()  # interrupt any restart-backoff sleep
        self._thread.join(timeout=30)

    def release_device_state(self) -> None:
        """Drop the engine's device allocations — the KV state (the big
        one: dl/kv_layout.py says how big), the token vector, and every
        compiled-program reference.
        Call AFTER ``close()``: the model-unload path (dl/lifecycle.py)
        must return the HBM to the pool budget immediately, not when the
        garbage collector eventually notices the dead engine."""
        if not self._closed:
            raise RuntimeError("release_device_state requires close() first")
        self._cache = None
        self._tok = None
        for attr in ("_admit_prog", "_admit_cached_prog", "_admit_many_prog",
                     "_chunk", "_chunk_prog", "_piece_prog", "_piece_flip_prog",
                     "_seed_prog", "_snap_prog", "_spec_prog"):
            setattr(self, attr, None)
