"""Continuous-batching decode engine over the genesys.pagedkv pool.

The closed-batch path (``serve_model(batch_decode=True)``) only batches
requests that arrive in the same poll and holds the bucket's shape until
its LONGEST request finishes — late arrivals wait, early finishers pad.
This engine decodes at one FIXED padded batch shape forever:

  * ``n_slots`` decode slots; a request occupies one slot from admission
    to retirement. Admission and retirement happen **mid-decode** — they
    mutate only a slot's block-table row, ``cache_len`` and current
    token, never an array shape, so membership churn cannot re-jit
    (``train.steps.make_paged_serve_step`` is compiled exactly once).
  * Inactive slots are masked by construction: their block-table rows
    are all null-block, their ``cache_len`` is 0, and their outputs are
    never read — no `where`-masking inside the step function needed.
  * KV lives in the paged arena; a slot's prompt prefix can start
    mid-cache when :class:`~repro.serving.pagedkv.PagedKVPool` has the
    prefix's blocks sealed (shared-prefix reuse skips those positions
    entirely).

Prompts are written in chunks: a second fixed-shape program
(``train.steps.make_paged_prefill_chunk``) writes up to ``CHUNK`` prompt
tokens' KV into a slot's blocks in one dispatch. A slot admitted with
more than its last prompt token left to write joins a FIFO of
prefilling slots; each step dispatches at most one chunk, for the head
of the FIFO, covering prompt positions up to P - 2, and then the decode
step with no sync in between. Meanwhile the slot's decode row stays
null, like a free slot's. Once its KV reaches P - 1 positions, the slot
takes its real row with ``cache_len`` P - 1 and the LAST prompt token as
its current token: the decode step feeds that token and produces the
first generated token. Each generated token is fed back until the
request's budget is reached; the final token is returned but never fed
(its KV would be dead).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.genesys.trace import (
    EV_ADMIT, EV_FIRST_TOKEN, EV_MOE_ROUTE, EV_STEP, LOOP_ADVANCE,
    LOOP_DISPATCH, LOOP_RETIRE, LOOP_SYNC, MOE_ROUTE_SHIFT, REQ_SYSNO,
    Counters,
)
from repro.serving.pagedkv import NULL_BLOCK, PagedKVPool, PoolExhausted

CHUNK = 256     # prompt tokens one prefill dispatch writes (16 blocks of 16)


@dataclass
class EngineStats:
    admitted: int = 0
    retired: int = 0
    steps: int = 0               # serve_fn dispatches
    step_slots: int = 0          # sum of active slots over steps
    prefill_steps: int = 0       # prompt tokens fed by decode steps
    prefill_steps_saved: int = 0  # prompt tokens skipped via prefix reuse
    prefill_chunks: int = 0      # prefill_chunk dispatches
    prefill_chunk_tokens: int = 0  # prompt tokens the chunks wrote
    # bytes the compiled step and chunk alias from input to output (set
    # once by make_engine): twice the arenas' bytes when both update
    # them in place, 0 without jit or where the backend reports none
    arena_alias_bytes: int = 0

    def occupancy(self) -> float:
        return self.step_slots / max(1, self.steps)


@dataclass
class _Slot:
    meta: object = None
    prompt: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int32))
    feed_idx: int = 0
    budget: int = 0
    gen: list = field(default_factory=list)
    blocks: list = field(default_factory=list)
    cache_len: int = 0
    span: int = 0                 # request-scoped trace span id (0 = none)


class ContinuousBatchEngine:
    """Slot-scheduled continuous batching over a paged KV arena."""

    def __init__(self, serve_step, params, arenas, pool: PagedKVPool, *,
                 prefill_chunk, n_slots: int, max_blocks_per_seq: int,
                 stats=None):
        self.serve_step = serve_step
        self.prefill_chunk = prefill_chunk
        self.params = params
        # the family's entries, each [L,NB,BS,...] (init_paged_arena)
        self.arenas = arenas
        self.pool = pool
        self.n_slots = int(n_slots)
        self.max_blocks = int(max_blocks_per_seq)
        self.block_size = pool.block_size
        if any(a.shape[1] != pool.n_blocks for a in arenas.values()):
            raise ValueError("arena/pool block-count mismatch")
        if any(a.shape[2] != pool.block_size for a in arenas.values()):
            raise ValueError("arena/pool block-size mismatch")
        # every chunk starts on a block boundary (a reused prefix is whole
        # blocks), so no chunk writes into a shared sealed block
        if CHUNK % self.block_size:
            raise ValueError(f"block size {self.block_size} does not "
                             f"divide the prefill chunk {CHUNK}")
        # fixed-shape schedule state: one row per slot, shapes NEVER change
        self._bt = np.zeros((self.n_slots, self.max_blocks), np.int32)
        self._cl = np.zeros((self.n_slots,), np.int32)
        self._cur = np.zeros((self.n_slots, 1), np.int32)
        self._slots: list[_Slot | None] = [None] * self.n_slots
        self._prefilling: deque[int] = deque()  # slots awaiting chunks
        # trace.Counters fold: telemetry snapshots of engine stats are
        # torn-read-free even while the decode loop runs (attach_stats)
        self.counters = Counters(EngineStats())
        if stats is not None and not isinstance(stats, Counters):
            stats = Counters(stats)
        self.serve_stats = stats      # optional server-side Counters
        # request-scoped tracing: the server sets this TraceChannel; each
        # decode dispatch records one EV_STEP per active span, and
        # retirement syscalls run under the request's span context
        self.trace = None
        # optional genesys.admit AdmissionController: admission failures
        # for want of capacity nudge its shed level up (note_pressure) —
        # a leading overload signal, ahead of SLO burn confirming it
        self.admission = None
        self._step_idx = 0
        # wire the pool's eviction spill to the device arenas
        pool.extractor = self._extract_block

    @property
    def stats(self) -> EngineStats:
        return self.counters.stats

    @stats.setter
    def stats(self, new) -> None:
        # benchmarks reset via ``eng.stats = EngineStats()``; swapping the
        # wrapped object under the lock keeps every attached reference
        # (telemetry, collectors) reading the live record
        with self.counters.lock:
            self.counters.stats = new

    # ------------------------------------------------------- introspection --
    @property
    def n_active(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    @property
    def free_slots(self) -> int:
        return self.n_slots - self.n_active

    # --------------------------------------------------- arena <-> bytes ----
    # a block's bytes: each entry's [L, BS, ...] slice, in key order
    def block_bytes(self) -> int:
        return sum(int(np.prod(a.shape)) // a.shape[1] * a.dtype.itemsize
                   for a in self.arenas.values())

    def _extract_block(self, bid: int) -> bytes:
        return b"".join(np.asarray(self.arenas[name][:, bid]).tobytes()
                        for name in sorted(self.arenas))

    def _install_block(self, bid: int, payload: bytes) -> None:
        at = 0
        for name in sorted(self.arenas):
            a = self.arenas[name]
            shape = (a.shape[0],) + a.shape[2:]
            dt = np.dtype(a.dtype)
            n = int(np.prod(shape)) * dt.itemsize
            b = np.frombuffer(payload[at:at + n], dtype=dt).reshape(shape)
            self.arenas[name] = a.at[:, bid].set(jnp.asarray(b))
            at += n

    # ----------------------------------------------------------- admission --
    def admit(self, prompt, n_tokens: int, meta=None,
              span: int = 0, tag: int | None = None) -> bool:
        """Claim a slot for a request mid-decode. Returns False (admitting
        nothing) when no slot or not enough arena blocks are available —
        the caller keeps the request queued and retries after retirements.
        With tracing on, success records ``EV_ADMIT`` for ``span``
        carrying the request's wire ``tag`` (-1 for a request without
        one).
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n, budget = len(prompt), max(1, int(n_tokens))
        if n < 1:
            return False
        total_pos = n + budget - 1          # KV positions this request writes
        bs = self.block_size
        n_blocks = -(-total_pos // bs)
        if n_blocks > self.max_blocks:
            raise ValueError(
                f"request needs {n_blocks} blocks > table width "
                f"{self.max_blocks}")
        slot = next((i for i, s in enumerate(self._slots) if s is None), None)
        if slot is None:
            if self.admission is not None:
                self.admission.note_pressure()
            return False
        # prefix reuse: only WHOLE blocks strictly before the last prompt
        # token (at least one token must remain to feed, and writes must
        # never land inside a shared block)
        reuse_span = ((n - 1) // bs) * bs
        reused, fetches = self.pool.acquire_prefix(prompt[:reuse_span])
        try:
            fresh = self.pool.alloc(n_blocks - len(reused))
        except PoolExhausted:
            self.pool.retire(reused)        # sealed blocks re-park in LRU
            if self.admission is not None:
                self.admission.note_pressure()
            return False
        for bid, payload in fetches:
            self._install_block(bid, payload)
        blocks = reused + fresh
        r = len(reused) * bs                # cache positions already filled
        st = _Slot(meta=meta, prompt=prompt, budget=budget, blocks=blocks,
                   cache_len=r, span=span)
        self._slots[slot] = st
        if n - r > 1:
            self._prefilling.append(slot)   # its decode row stays null
        else:
            self._seat(slot, st)
        self.counters.add(admitted=1, prefill_steps_saved=r)
        ch = self.trace
        if ch is not None:
            ch.rec(EV_ADMIT, REQ_SYSNO, span,
                   aux=-1 if tag is None else tag)
        return True

    def _seat(self, slot: int, st: _Slot) -> None:
        """Give a slot its real row in the decode inputs: the next step
        feeds ``prompt[cache_len]``, the last prompt token."""
        self._bt[slot, :] = NULL_BLOCK
        self._bt[slot, :len(st.blocks)] = st.blocks
        self._cl[slot] = st.cache_len
        self._cur[slot, 0] = st.prompt[st.cache_len]
        st.feed_idx = st.cache_len + 1

    def _dispatch_chunk(self) -> None:
        """Write the next ``CHUNK`` prompt positions of the FIFO's head
        slot, stopping before its last prompt token; seat the slot once
        its chunks are done. Dispatch only: nothing waits on the device."""
        slot = self._prefilling[0]
        st = self._slots[slot]
        start = st.cache_len
        end = min(start + CHUNK, len(st.prompt) - 1)
        tokens = np.zeros((1, CHUNK), np.int32)
        tokens[0, :end - start] = st.prompt[start:end]
        row = np.full((1, self.max_blocks), NULL_BLOCK, np.int32)
        row[0, :len(st.blocks)] = st.blocks
        # numpy inputs: a jnp.asarray of a Python list compiles a program
        start_a = np.array([start], np.int32)
        n_valid = np.array([end - start], np.int32)
        self.arenas = self.prefill_chunk(
            self.params, self.arenas, jnp.asarray(row), jnp.asarray(tokens),
            jnp.asarray(start_a), jnp.asarray(n_valid))
        st.cache_len = end
        self.counters.add(prefill_chunks=1, prefill_chunk_tokens=end - start)
        if end == len(st.prompt) - 1:
            self._prefilling.popleft()
            self._seat(slot, st)

    def _retire(self, slot: int, st: _Slot) -> None:
        ch = self.trace
        if ch is not None and st.span:
            # retirement syscalls (MADVISE frees, spill PWRITE64s) are
            # attributed to the request that caused them
            with ch.tracer.span(st.span):
                self.pool.retire(st.blocks, prompt_tokens=st.prompt)
        else:
            self.pool.retire(st.blocks, prompt_tokens=st.prompt)
        self._slots[slot] = None
        self._bt[slot, :] = NULL_BLOCK
        self._cl[slot] = 0
        self._cur[slot, 0] = 0
        self.counters.add(retired=1)

    # ---------------------------------------------------------- decoding ----
    def step(self, loop=None) -> list[tuple[object, list[int]]]:
        """At most one prefill chunk, then one fixed-shape decode dispatch
        for every seated slot; advances each of those slots and retires
        finished requests. While every busy slot is still prefilling, the
        chunk goes alone. Returns the ``(meta, generated_tokens)`` pairs
        that completed on this step. ``loop`` is the serving loop's
        :class:`~repro.core.genesys.trace.LoopRecorder` (tracing on):
        the step marks its dispatch, sync, advance and retire phases."""
        if not self.n_active:
            return []
        if loop is not None:
            loop.phase(LOOP_DISPATCH)
        if self._prefilling:
            self._dispatch_chunk()
        active = [(i, s) for i, s in enumerate(self._slots)
                  if s is not None and i not in self._prefilling]
        if not active:
            return []
        t0 = time.perf_counter_ns()
        # an MoE step also returns the expert layers' hits
        nxt, self.arenas, *hits = self.serve_step(
            self.params, self.arenas, jnp.asarray(self._bt),
            jnp.asarray(self._cur), jnp.asarray(self._cl))
        if loop is not None:
            loop.phase(LOOP_SYNC)
        if hits:
            nxt, hits = jax.device_get((nxt, hits[0]))
        else:
            nxt, hits = np.asarray(nxt), None
        dur = time.perf_counter_ns() - t0
        if loop is not None:
            loop.phase(LOOP_ADVANCE)
        self.counters.add(steps=1, step_slots=len(active))
        if self.serve_stats is not None:
            self.serve_stats.add(decode_dispatches=1,
                                 decode_steps=len(active))
        done = []
        prefills = 0
        for i, st in active:
            st.cache_len += 1               # the fed token's KV landed
            if st.feed_idx < len(st.prompt):
                # still consuming the prompt (teacher forcing)
                self._cur[i, 0] = st.prompt[st.feed_idx]
                st.feed_idx += 1
                prefills += 1
            else:
                st.gen.append(int(nxt[i]))
                if len(st.gen) >= st.budget:
                    done.append((i, st))
                    continue
                self._cur[i, 0] = st.gen[-1]
            self._cl[i] = st.cache_len
        if prefills:
            self.counters.add(prefill_steps=prefills)
        ch = self.trace
        if ch is not None:
            # one self-contained EV_STEP per active request span: ts is
            # the dispatch start, aux the duration (ns) — no begin/end
            # pair to join, since a span repeats its seq across steps
            spans = [s.span for _, s in active if s.span]
            if spans:
                ch.rec_block(EV_STEP, self._step_idx, spans, aux=dur,
                             ts=t0, own=True)
            # a slot holds one generated token only on the step that
            # produced its first
            firsts = [s.span for _, s in active
                      if s.span and len(s.gen) == 1]
            if firsts:
                ch.rec_block(EV_FIRST_TOKEN, REQ_SYSNO, firsts,
                             aux=self._step_idx, ts=t0 + dur, own=True)
            if hits is not None:
                self._record_routes(ch, hits, t0)
        self._step_idx += 1
        if loop is not None:
            loop.phase(LOOP_RETIRE)
        for i, st in done:
            self._retire(i, st)
        return [(st.meta, st.gen) for _, st in done]

    def _record_routes(self, ch, hits: np.ndarray, ts: int) -> None:
        """One ``EV_MOE_ROUTE`` per expert layer: the step in ``sysno``,
        the layer in ``seq``, and in ``aux`` the held experts hit plus the
        (row, held expert) assignments shifted by ``MOE_ROUTE_SHIFT``."""
        hits = hits.astype(np.int64)
        n_layers = next(iter(self.arenas.values())).shape[0]
        aux = (hits > 0).sum(1) + (hits.sum(1) << MOE_ROUTE_SHIFT)
        ch.rec_block(EV_MOE_ROUTE, self._step_idx,
                     np.arange(n_layers - len(hits), n_layers,
                               dtype=np.int64),
                     aux=aux, ts=ts, own=True)

    def drain(self) -> list[tuple[object, list[int]]]:
        """Run steps until every active request has retired."""
        out = []
        while self.n_active:
            out.extend(self.step())
        return out


def compile_programs(step, chunk, params, arenas, *, n_slots: int,
                     max_blocks: int, sharding=None):
    """Jit the paged decode step and the prefill chunk with the arenas
    donated (argument 1), so both update them in place, and compile each
    at its one shape: ``n_slots`` decode rows, ``max_blocks`` table
    columns, ``CHUNK`` prompt tokens. ``sharding`` places the int32
    inputs (default: uncommitted, as the engine's numpy-built ones are).
    -> (compiled step, compiled chunk)."""
    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    step = jax.jit(step, donate_argnums=1).lower(
        params, arenas, i32(n_slots, max_blocks), i32(n_slots, 1),
        i32(n_slots)).compile()
    chunk = jax.jit(chunk, donate_argnums=1).lower(
        params, arenas, i32(1, max_blocks), i32(1, CHUNK), i32(1),
        i32(1)).compile()
    return step, chunk


def alias_bytes(compiled) -> int:
    """Bytes a compiled program aliases from its inputs to its outputs
    (0 where the backend gives no memory analysis)."""
    m = compiled.memory_analysis()
    return int(getattr(m, "alias_size_in_bytes", 0) or 0)


def make_engine(cfg, rules, params, *, n_slots: int, n_blocks: int,
                block_size: int, max_blocks_per_seq: int | None = None,
                gsys=None, spill_path: str | None = None, stats=None,
                jit=True):
    """Build the paged pool, device arenas and the jitted paged serve step
    and prefill chunk into a ready :class:`ContinuousBatchEngine`. Both
    programs are compiled here, at their one shape, so a window's first
    step and first prompt wait for no compile; the arenas are donated to
    both, so no second arena copy is ever live. With ``gsys`` the pool's
    blocks are carved through genesys (mmap/touch/madvise residency, and
    — given ``spill_path`` — PWRITE64 spill + PREAD64_FIXED revival)."""
    from repro.models import transformer
    from repro.train.steps import (make_paged_prefill_chunk,
                                   make_paged_serve_step)

    arenas = transformer.init_paged_arena(cfg, n_blocks, block_size)
    pool = PagedKVPool(n_blocks, block_size)
    max_blocks = max_blocks_per_seq or n_blocks // 2
    step = make_paged_serve_step(cfg, rules)
    chunk = make_paged_prefill_chunk(cfg, rules)
    aliased = 0
    if jit:
        step, chunk = compile_programs(step, chunk, params, arenas,
                                       n_slots=n_slots, max_blocks=max_blocks)
        aliased = alias_bytes(step) + alias_bytes(chunk)
    eng = ContinuousBatchEngine(
        step, params, arenas, pool, prefill_chunk=chunk, n_slots=n_slots,
        max_blocks_per_seq=max_blocks, stats=stats)
    eng.counters.add(arena_alias_bytes=aliased)
    if gsys is not None:
        pool.bind_genesys(gsys, block_bytes=eng.block_bytes(),
                          spill_path=spill_path)
        gsys.attach_stats("engine", eng.counters)
    return eng
