"""Serving engines: static batched decode and paged continuous batching.

``ServeEngine`` is the static path: one batch, prompts tail-padded to a
common length, a dense ``(B, max_len)`` KV cache, lockstep decode until
the batch's token budget is exhausted.  Mixed-length prompts are handled
honestly (per-sequence ``lengths`` thread through prefill; decode masks
each sequence's own live cache length) but the *memory* is still padded
capacity and the *schedule* still runs the whole batch until the slowest
request finishes.

``PagedServeEngine`` is the continuous-batching path (DESIGN.md §9):
KV storage is a pool of fixed-size blocks (``serve/paging.py``), decode
lanes are slots that requests flow through — admission fills free slots
each step, long prompts prefill chunk-by-chunk so they never stall the
decode batch, finished sequences release their blocks immediately.
Decode attention gathers K/V through per-sequence block tables (the
Pallas ``kernels/paged_attention.py`` kernel on TPU).

Overload robustness (DESIGN.md §14): the paged engine degrades instead
of crashing.  Every request ends in a typed terminal status
(``OK | SHED | TIMEOUT | CANCELLED | ERROR``); admission is bounded and
shedding, deadlines and ``cancel(rid)`` free resources deterministically,
and when the block pool runs dry a victim policy preempts a lane —
swapping its live KV blocks + SSM slot state to a host-side ``SwapPool``
(bit-exact restore) or falling back to recompute-preemption when the
swap pool is full.  A ``ChaosHooks`` seam (``serve/chaos.py``) injects
faults at each of these points for the fault-isolation tests.

Both engines report jit compile time separately (``compile_s``) so
``tok_per_s`` measures steady-state decode, not compilation.
"""
from __future__ import annotations

import enum
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import ArchConfig, get_model

from .chaos import ChaosError
from .paging import (BlockAllocator, BlockTables, PagingError, SwapEntry,
                     SwapPool, checksum_arrays)


@dataclass
class ServeStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    compile_s: float = 0.0     # jit compile + first-call warmup, reported
    tokens_out: int = 0        # tokens produced by TIMED decode steps (each
    steps: int = 0             # request's first token comes from prefill
    peak_cache_blocks: int = 0   # logits and is counted by neither engine)
    peak_cache_bytes: int = 0    # paged engine only
    # per-request latency accounting (paged engine; DESIGN.md §11):
    # TTFT = enqueue -> first token, TPOT = mean inter-token time after
    # the first, queue_wait = enqueue -> admission.  Seconds.
    ttft_p50: float = 0.0
    ttft_p99: float = 0.0
    tpot_p50: float = 0.0
    tpot_p99: float = 0.0
    queue_wait_p50: float = 0.0
    queue_wait_p99: float = 0.0
    # lifecycle accounting for THIS run (DESIGN.md §14)
    preempted: int = 0         # lane evictions (swap or recompute)
    restored: int = 0          # preempted requests resumed
    shed: int = 0              # admission rejections (typed, never raised)
    timeouts: int = 0          # deadline expiries
    cancelled: int = 0
    errors: int = 0            # faulted requests isolated to terminal ERROR
    swap_peak_blocks: int = 0  # host swap pool high-water mark
    goodput_tokens: int = 0    # decode tokens of requests that ended OK

    @property
    def tok_per_s(self):
        return self.tokens_out / self.decode_s if self.decode_s else 0.0

    @property
    def goodput_tok_per_s(self):
        return self.goodput_tokens / self.decode_s if self.decode_s else 0.0


class ServeEngine:
    """Static batch engine: dense padded cache, lockstep decode."""

    def __init__(self, cfg: ArchConfig, params, max_len: int = 512):
        self.cfg = cfg
        self.model = get_model(cfg)
        self.params = params
        self.max_len = max_len
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(p, b, pad_to=max_len))
        self._decode = jax.jit(self.model.decode)

    def pad_batch(self, prompts: list[list[int]], pad_to: int | None = None):
        """Tail-pad prompts to a common length.  Returns (tokens (B, L),
        lengths (B,)) — the lengths ride along so prefill takes each
        sequence's logits at its OWN last token and decode masks the pad
        tail (pad id 0 is a real vocab id; masking, not the pad value,
        is what keeps it out of attention).  ``pad_to`` fixes L across
        batches so multi-batch serving compiles prefill once."""
        L = max(max(len(p) for p in prompts), pad_to or 0)
        toks = np.zeros((len(prompts), L), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        lengths = np.asarray([len(p) for p in prompts], np.int32)
        return jnp.asarray(toks), jnp.asarray(lengths)

    def generate(self, prompts: list[list[int]], max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 top_k: int | None = None, top_p: float | None = None,
                 extra_inputs: dict | None = None, warmup: bool = True,
                 pad_prompts_to: int | None = None):
        """Returns (tokens (B, max_new_tokens), ServeStats)."""
        toks, lengths = self.pad_batch(prompts, pad_to=pad_prompts_to)
        batch = {"tokens": toks, "lengths": lengths, **(extra_inputs or {})}
        stats = ServeStats()
        if warmup:
            # compile both steps on the real shapes; one throwaway
            # execution each (compile dominates) keeps tok_per_s honest
            t0 = time.time()
            logits, cache = self._prefill(self.params, batch)
            wtok = jnp.zeros((len(prompts), 1), jnp.int32)
            wl, _ = self._decode(self.params, cache, {"tokens": wtok})
            jax.block_until_ready(wl)
            stats.compile_s = time.time() - t0

        t0 = time.time()
        logits, cache = self._prefill(self.params, batch)
        logits.block_until_ready()
        stats.prefill_s = time.time() - t0

        key = jax.random.PRNGKey(seed)
        out = []
        t0 = time.time()
        for i in range(max_new_tokens):
            if temperature > 0 and (top_k is not None or top_p is not None):
                from repro.kernels.ops import sample_tokens
                key, sub = jax.random.split(key)
                u = jax.random.uniform(sub, (logits.shape[0],))
                nxt = sample_tokens(logits, u, temperature=temperature,
                                    top_k=top_k, top_p=top_p)
            elif temperature > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits / temperature, -1)
            else:
                nxt = jnp.argmax(logits, -1)
            out.append(nxt)
            logits, cache = self._decode(self.params, cache,
                                         {"tokens": nxt[:, None].astype(jnp.int32)})
        jax.block_until_ready(logits)
        stats.decode_s = time.time() - t0
        stats.steps = max_new_tokens
        # first tokens are prefill-derived — same accounting as the paged
        # engine so --paged / static tok_per_s compare apples to apples
        stats.tokens_out = len(prompts) * max(0, max_new_tokens - 1)
        return np.stack([np.asarray(t) for t in out], axis=1), stats


# ---------------------------------------------------------------------------
# continuous batching


class Status(enum.Enum):
    """Typed terminal status — every request ends in exactly one of
    these (DESIGN.md §14 state machine); exceptions are reserved for
    engine invariant violations, never for overload."""
    OK = "OK"
    SHED = "SHED"
    TIMEOUT = "TIMEOUT"
    CANCELLED = "CANCELLED"
    ERROR = "ERROR"


# typed rejection reason codes carried by Ticket / RequestResult.reason
REJECT_QUEUE_FULL = "QUEUE_FULL"
REJECT_PROMPT_TOO_LONG = "PROMPT_TOO_LONG"
REJECT_EVICTED = "EVICTED"      # shed from the queue by a higher priority


class ServeError(RuntimeError):
    """The engine could not drain its queue (stuck scheduler).  Carries
    the stuck request ids and the allocator occupancy so the failure is
    actionable instead of a bare RuntimeError."""

    def __init__(self, msg: str, stuck_rids=(), blocks_in_use: int = 0,
                 num_free: int = 0):
        self.stuck_rids = list(stuck_rids)
        self.blocks_in_use = blocks_in_use
        self.num_free = num_free
        super().__init__(
            f"{msg}: stuck rids {self.stuck_rids}, "
            f"{blocks_in_use} blocks in use, {num_free} free")


@dataclass
class Ticket:
    """Admission result — ``add_request`` never raises on overload.
    ``accepted=False`` carries a typed ``reason`` code (QUEUE_FULL /
    PROMPT_TOO_LONG), a human ``detail``, and for queue rejections a
    ``retry_after_s`` backoff hint."""
    rid: int
    accepted: bool
    reason: str = ""
    detail: str = ""
    retry_after_s: float | None = None


@dataclass
class RequestResult:
    """Terminal record for one request (``engine.results[rid]``)."""
    rid: int
    status: Status
    tokens: list[int]
    reason: str = ""
    preemptions: int = 0
    deadline_miss_s: float | None = None


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    out: list[int] = field(default_factory=list)
    prefilled: int = 0          # seq tokens already in the cache
    priority: int = 0           # higher = more important (preempts lower)
    deadline: float | None = None   # absolute perf_counter() deadline
    # ``seq`` is what prefill rebuilds: the prompt, or after a
    # recompute-preemption the prompt + already-emitted tokens (minus the
    # last, which re-enters as the next decode input)
    seq: list[int] = field(default_factory=list)
    emit_first: bool = True     # sample a first token when prefill ends
    n_preempted: int = 0
    reserved_pages: int = 0     # worst-case reservation (reserve mode)
    admit_seq: int = -1         # admission order (LIFO victim policy)
    # lifecycle stamps (time.perf_counter(); obs layer, DESIGN.md §11)
    t_enq: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0        # first token sampled (prefill logits)
    t_done: float = 0.0

    def __post_init__(self):
        if not self.seq:
            self.seq = list(self.prompt)

    @property
    def done(self) -> bool:
        return len(self.out) >= self.max_new_tokens


def _add_expert_counts(counts: dict, got: dict) -> None:
    """Fold one program's expert counts into the step's span args."""
    counts["expert_rows"] += int(got["expert_rows"])
    counts["expert_rows_max"] = max(counts["expert_rows_max"],
                                    int(got["expert_rows_max"]))


class PagedServeEngine:
    """Paged KV-cache + continuous-batching decode (DESIGN.md §9, §14).

    ``max_batch`` decode lanes over a block pool of ``num_blocks`` blocks
    of ``block_size`` tokens (block 0 is the sink).  Two admission modes:

    * ``admission="reserve"`` (default): a request is admitted only when
      its worst-case block need (prompt + generation budget) fits
      alongside every other admitted request's — deadlock-free by
      construction, but conservative: short actual generations strand
      reserved blocks.
    * ``admission="optimistic"``: only the *prompt* has to fit at
      admission; decode-time growth is backstopped by preemption — when
      the pool runs dry a victim policy (``lowest_priority`` /
      ``most_blocks`` / ``lifo``) evicts a strictly-lower-precedence
      lane, swapping its KV blocks + SSM state to the host ``SwapPool``
      (``swap_blocks`` capacity; bit-exact restore) or dropping them for
      recompute when the pool is full.  The highest-precedence live
      request is never a victim, which is the progress guarantee: it can
      always grow (evicting everyone else if needed), so it finishes,
      frees its blocks, and precedence passes on — no deadlock.

    Long prompts prefill at most ``prefill_chunks_per_step`` chunks of
    ``prefill_chunk`` tokens per engine step, interleaved with decode
    steps for the already-running lanes.
    """

    def __init__(self, cfg: ArchConfig, params, *, block_size: int = 16,
                 max_batch: int = 8, max_len: int = 512,
                 prefill_chunk: int = 64, num_blocks: int | None = None,
                 prefill_chunks_per_step: int = 1, kv_dtype=None,
                 top_k: int | None = None, top_p: float | None = None,
                 admission: str = "reserve", swap_blocks: int = 0,
                 victim_policy: str = "lowest_priority",
                 max_queue: int | None = None,
                 shed_policy: str = "reject_newest", chaos=None):
        if cfg.encoder_layers or cfg.frontend_tokens:
            raise ValueError("paged serving supports decoder-only text "
                             "archs (no enc-dec / multimodal prefixes)")
        if admission not in ("reserve", "optimistic"):
            raise ValueError(f"unknown admission mode {admission!r}")
        if victim_policy not in ("lowest_priority", "most_blocks", "lifo"):
            raise ValueError(f"unknown victim policy {victim_policy!r}")
        if shed_policy not in ("reject_newest", "evict_lowest"):
            raise ValueError(f"unknown shed policy {shed_policy!r}")
        self.cfg = cfg
        self.model = get_model(cfg)
        self.params = params
        self.block_size = block_size
        self.max_batch = max_batch
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.prefill_chunks_per_step = prefill_chunks_per_step
        # "int8"/"fp8_e4m3"/"fp8_e5m2" quantize the KV pools with per-row
        # scale tensors riding alongside (DESIGN.md §13); None = native
        self.kv_dtype = None if kv_dtype == "native" else kv_dtype
        self.top_k = top_k
        self.top_p = top_p
        self.admission = admission
        self.victim_policy = victim_policy
        self.max_queue = max_queue
        self.shed_policy = shed_policy
        self.chaos = chaos
        self.max_pages = -(-max_len // block_size)
        if num_blocks is None:
            num_blocks = max_batch * self.max_pages + 1   # +1: sink
        self.alloc = BlockAllocator(num_blocks, block_size, chaos=chaos)
        self.tables = BlockTables(self.alloc, max_batch, self.max_pages)
        self.swap = SwapPool(swap_blocks)
        self.cache = self.model.make_paged_cache(num_blocks, block_size,
                                                 max_batch,
                                                 kv_dtype=self.kv_dtype)
        self._decode = jax.jit(self.model.decode_paged, donate_argnums=(1,))
        self._chunk = jax.jit(self.model.prefill_chunk_paged,
                              donate_argnums=(1,))
        self.pos = np.zeros(max_batch, np.int64)   # tokens in cache per lane
        self.slots: list[Request | None] = [None] * max_batch
        self.pending: list[Request] = []
        self.preempted: list[Request] = []         # waiting to restore
        self.completed: dict[int, list[int]] = {}  # rid -> emitted tokens
        self.results: dict[int, RequestResult] = {}  # rid -> terminal record
        self._last_logits: dict[int, jax.Array] = {}   # slot -> (V,) logits
        self._reserved_blocks = 0
        self._next_rid = 0
        self._admit_counter = 0
        self._avg_service_s = 0.0      # EMA of admit->done (retry hints)
        self._counts = {"preempted": 0, "restored": 0, "shed": 0,
                        "timeout": 0, "cancelled": 0, "error": 0,
                        "decode_faults": 0}
        # run() reports counts/goodput since the PREVIOUS run's end, so
        # lifecycle events between runs (add_request sheds, cancels)
        # attribute to the next run's ServeStats
        self._counts_mark = dict(self._counts)
        self._results_mark: set[int] = set()
        self._key = jax.random.PRNGKey(0)
        self.temperature = 0.0
        # obs (DESIGN.md §11): lifecycle spans land on per-request tracks
        # ("req<rid>"), engine steps on "serve"; TTFT/TPOT/queue-wait
        # histograms live in the process metrics registry.  _observe is
        # dropped during warmup so the throwaway request pollutes nothing.
        self._observe = True

    # -- obs helpers --------------------------------------------------------
    @staticmethod
    def _hist(name: str):
        return obs.get_metrics().histogram(name)

    def _count(self, key: str):
        self._counts[key] += 1
        if self._observe:
            obs.get_metrics().counter(f"serve.{key}").inc()

    def _req_track(self, req: Request) -> str:
        return f"req{req.rid}"

    # -- request lifecycle --------------------------------------------------
    @staticmethod
    def _precedence(req: Request):
        """Scheduling order: higher priority first, then FIFO.  Strict
        total order — the basis of the no-deadlock argument (a lane may
        only preempt strictly-lower-precedence lanes)."""
        return (-req.priority, req.rid)

    def add_request(self, prompt: list[int], max_new_tokens: int, *,
                    priority: int = 0,
                    deadline_ms: float | None = None) -> Ticket:
        """Enqueue a request.  NEVER raises on overload or an unservable
        request — the returned ``Ticket`` carries a typed rejection
        (``QUEUE_FULL`` with a retry-after hint, ``PROMPT_TOO_LONG``)
        and the request is recorded as terminal ``SHED``.  ``PagingError``
        stays reserved for true allocator invariant violations."""
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, list(prompt), max_new_tokens, priority=priority,
                      t_enq=time.perf_counter())
        if deadline_ms is not None:
            req.deadline = req.t_enq + deadline_ms / 1e3
        need = self.tables.pages_for(len(prompt) + max_new_tokens)
        if (len(prompt) + max_new_tokens > self.max_len
                or need > self.max_pages
                or need > self.alloc.num_blocks - 1):
            return self._reject(
                req, REJECT_PROMPT_TOO_LONG,
                f"prompt({len(prompt)}) + new({max_new_tokens}) needs "
                f"{need} blocks; limits: max_len={self.max_len}, "
                f"pool={self.alloc.num_blocks - 1} blocks of "
                f"{self.block_size}")
        if self.max_queue is not None and len(self.pending) >= self.max_queue:
            if self.shed_policy == "evict_lowest":
                victim = max(self.pending, key=self._precedence)
                if self._precedence(victim) > self._precedence(req):
                    self.pending.remove(victim)
                    self._record_terminal(victim, Status.SHED,
                                          REJECT_EVICTED)
                    self._count("shed")
                else:
                    return self._reject(req, REJECT_QUEUE_FULL,
                                        f"queue at max_queue="
                                        f"{self.max_queue} and no lower-"
                                        f"priority request to evict")
            else:
                return self._reject(req, REJECT_QUEUE_FULL,
                                    f"queue at max_queue={self.max_queue}")
        self.pending.append(req)
        if self._observe:
            obs.get_recorder().instant(
                "enqueued", cat="serve", track=self._req_track(req),
                prompt_len=len(prompt), budget=max_new_tokens,
                priority=priority)
        return Ticket(rid, True)

    def _reject(self, req: Request, code: str, detail: str) -> Ticket:
        self._record_terminal(req, Status.SHED, code)
        self._count("shed")
        hint = self._retry_after_hint() if code == REJECT_QUEUE_FULL else None
        return Ticket(req.rid, False, reason=code, detail=detail,
                      retry_after_s=hint)

    def _retry_after_hint(self) -> float:
        """Rough queue-drain estimate: recent per-request service time x
        queue depth / lanes — a backoff hint, not a promise."""
        per = self._avg_service_s or 0.05
        return max(0.01, per * (len(self.pending) + 1) / self.max_batch)

    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it is (queued, running, preempted);
        blocks / slot / SSM state / swap entry are freed immediately.
        Returns False if the rid is unknown or already terminal."""
        for req in self.pending:
            if req.rid == rid:
                self.pending.remove(req)
                self._record_terminal(req, Status.CANCELLED, "in queue")
                self._count("cancelled")
                return True
        for slot, r in enumerate(self.slots):
            if r is not None and r.rid == rid:
                self._finish_slot(slot, Status.CANCELLED, "while running")
                return True
        for req in self.preempted:
            if req.rid == rid:
                self.preempted.remove(req)
                if rid in self.swap:
                    self.swap.pop(rid)
                self._record_terminal(req, Status.CANCELLED,
                                      "while preempted")
                self._count("cancelled")
                return True
        return False

    def _record_terminal(self, req: Request, status: Status, reason: str):
        """Every request's endpoint: one typed RequestResult, exactly
        once.  Resource release is the caller's job (it differs by where
        the request was: slot, queue, or swap pool)."""
        if req.t_done == 0.0:
            req.t_done = time.perf_counter()
        miss = None
        if req.deadline is not None and req.t_done > req.deadline:
            miss = req.t_done - req.deadline
            if self._observe:
                self._hist("serve.deadline_miss_s").observe(miss)
        self.results[req.rid] = RequestResult(
            req.rid, status, list(req.out), reason, req.n_preempted, miss)
        self.completed[req.rid] = list(req.out)
        if self._observe and status is not Status.OK:
            obs.get_recorder().instant(status.value.lower(), cat="serve",
                                       track=self._req_track(req),
                                       reason=reason)

    def _worst_case_pages(self, req: Request) -> int:
        return self.tables.pages_for(len(req.prompt) + req.max_new_tokens)

    def _expire(self):
        """Deadline sweep over every live home a request can be in."""
        now = time.perf_counter()
        for req in [r for r in self.pending
                    if r.deadline is not None and now > r.deadline]:
            self.pending.remove(req)
            req.t_done = now
            self._record_terminal(req, Status.TIMEOUT, "in queue")
            self._count("timeout")
        for slot, r in enumerate(self.slots):
            if r is not None and r.deadline is not None and now > r.deadline:
                self._finish_slot(slot, Status.TIMEOUT, "while running")
        for req in [r for r in self.preempted
                    if r.deadline is not None and now > r.deadline]:
            self.preempted.remove(req)
            if req.rid in self.swap:
                self.swap.pop(req.rid)
            req.t_done = now
            self._record_terminal(req, Status.TIMEOUT, "while preempted")
            self._count("timeout")

    def _admit(self):
        self.pending.sort(key=self._precedence)
        for slot in range(self.max_batch):
            if self.slots[slot] is not None or not self.pending:
                continue
            req = self.pending[0]
            if self.admission == "reserve":
                need = self._worst_case_pages(req)
                if self._reserved_blocks + need > self.alloc.num_blocks - 1:
                    break               # head-of-line: keep precedence order
                req.reserved_pages = need
                self._reserved_blocks += need
            else:
                # optimistic: the PROMPT has to fit now; the generation
                # budget rides the preemption backstop (DESIGN.md §14)
                if self.tables.pages_for(len(req.seq)) > self.alloc.num_free:
                    break
            self.pending.pop(0)
            self._place(req, slot)

    def _place(self, req: Request, slot: int):
        self.slots[slot] = req
        self.pos[slot] = 0
        req.prefilled = 0
        req.t_admit = time.perf_counter()
        if req.admit_seq < 0:
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
        if self._observe:
            rec = obs.get_recorder()
            rec.complete("queued", rec.to_us(req.t_enq),
                         rec.to_us(req.t_admit), cat="serve",
                         track=self._req_track(req), slot=slot)
            self._hist("serve.queue_wait_s").observe(
                req.t_admit - req.t_enq)

    # -- preemption + swap (DESIGN.md §14) ----------------------------------
    def _pick_victim(self, cands: list[int]) -> int:
        if self.victim_policy == "most_blocks":
            key = lambda s: (-self.tables.n_pages(s),        # noqa: E731
                             -self.slots[s].admit_seq)
        elif self.victim_policy == "lifo":
            key = lambda s: -self.slots[s].admit_seq         # noqa: E731
        else:  # lowest_priority (FIFO-late tie break)
            key = lambda s: (self.slots[s].priority,         # noqa: E731
                             -self.slots[s].admit_seq)
        return min(cands, key=key)

    def preempt(self, rid: int) -> bool:
        """Evict a *running* request's lane (public primitive — the
        disaggregated-fleet router migrates lanes with this).  The
        request stays live: it re-enters via the preempted queue."""
        for slot, r in enumerate(self.slots):
            if r is not None and r.rid == rid:
                self._preempt_slot(slot)
                return True
        return False

    def _preempt_slot(self, slot: int):
        req = self.slots[slot]
        n = self.tables.n_pages(slot)
        use_swap = n > 0 and self.swap.can_hold(n)
        if use_swap:
            block_ids = [int(b) for b in self.tables.row(slot)[:n]]
            payload = self.model.paged_swap_out(self.cache, slot, block_ids)
            crcs = checksum_arrays(payload)     # pre-corruption truth
            if self.chaos is not None:
                self.chaos.on_swap_out(req.rid, payload)
            ll = self._last_logits.pop(slot, None)
            self.swap.put(SwapEntry(
                req.rid, n, payload, crcs, int(self.pos[slot]),
                req.prefilled,
                None if ll is None else np.asarray(ll)))
        else:
            # recompute-preemption: drop the blocks; restore re-prefills
            # prompt + emitted tokens (the last one re-enters as the next
            # decode input, so no first-token re-sample)
            self._last_logits.pop(slot, None)
            if req.out:
                req.seq = list(req.prompt) + req.out[:-1]
                req.emit_first = False
            req.prefilled = 0
        if req.reserved_pages:
            self._reserved_blocks -= req.reserved_pages
            req.reserved_pages = 0
        self.tables.release(slot)
        self.slots[slot] = None
        self.pos[slot] = 0
        req.n_preempted += 1
        self.preempted.append(req)
        self._count("preempted")
        if self._observe:
            obs.get_recorder().instant(
                "preempted", cat="serve", track=self._req_track(req),
                mode="swap" if use_swap else "recompute", blocks=n)
            obs.get_metrics().gauge("serve.swap_blocks_in_use").set(
                self.swap.in_use)

    def _free_by_preemption(self, requester_slot: int,
                            need_blocks: int) -> bool:
        """Preempt strictly-lower-precedence lanes (victim policy order)
        until ``need_blocks`` are free.  The precedence order is total,
        so the highest-precedence live request always finds victims or
        already owns the pool — the no-deadlock invariant."""
        req = self.slots[requester_slot]
        while self.alloc.num_free < need_blocks:
            cands = [s for s, r in enumerate(self.slots)
                     if r is not None and s != requester_slot
                     and self._precedence(r) > self._precedence(req)]
            if not cands:
                return False
            self._preempt_slot(self._pick_victim(cands))
        return True

    def _ensure_blocks(self, slot: int, length: int) -> bool:
        """Grow ``slot``'s table to cover ``length`` tokens; on a dry
        pool, preempt victims (optimistic mode's backstop).  False means
        the lane cannot run this step — it was preempted (waiting) or
        failed typed (chaos alloc fault -> terminal ERROR)."""
        want = self.tables.pages_for(length)
        need = want - self.tables.n_pages(slot)
        if need > 0 and self.alloc.num_free < need \
                and not self._free_by_preemption(slot, need):
            # no lower-precedence victim: the lane itself yields (its
            # progress is preserved by swap/recompute) and waits for
            # blocks to free up
            self._preempt_slot(slot)
            return False
        try:
            self.tables.ensure(slot, length)
            return True
        except ChaosError as e:         # injected device fault: isolate
            self._finish_slot(slot, Status.ERROR, f"alloc fault: {e}")
            return False
        except PagingError as e:        # invariant, not overload
            self._finish_slot(slot, Status.ERROR, f"alloc failed: {e}")
            return False

    def _restore_preempted(self):
        """Resume preempted requests (precedence order) into free slots.
        Swap restores need their block count + 1 free (the headroom
        keeps a restored lane from instantly re-preempting); recompute
        restores need their rebuilt prompt to fit, like admission."""
        if not self.preempted:
            return
        self.preempted.sort(key=self._precedence)
        for req in list(self.preempted):
            slot = next((s for s in range(self.max_batch)
                         if self.slots[s] is None), None)
            if slot is None:
                break
            if req.rid in self.swap:
                n = self.swap.blocks_of(req.rid)
                if self.alloc.num_free < n + 1:
                    continue
                entry = self.swap.pop(req.rid)
                self.preempted.remove(req)
                if not entry.verify():
                    self._record_terminal(
                        req, Status.ERROR,
                        "swap payload corrupt (crc mismatch)")
                    self._count("error")
                    continue
                try:
                    blocks = self.alloc.alloc(n)
                except ChaosError as e:
                    self._record_terminal(req, Status.ERROR,
                                          f"restore alloc fault: {e}")
                    self._count("error")
                    continue
                self.tables.adopt(slot, blocks)
                self.cache = self.model.paged_swap_in(self.cache, slot,
                                                      blocks, entry.arrays)
                self.slots[slot] = req
                self.pos[slot] = entry.pos
                req.prefilled = entry.prefilled
                if entry.last_logits is not None:
                    self._last_logits[slot] = jnp.asarray(entry.last_logits)
                mode = "swap"
            else:
                need = self.tables.pages_for(len(req.seq))
                if self.alloc.num_free < need + 1:
                    continue
                self.preempted.remove(req)
                self.slots[slot] = req
                self.pos[slot] = 0
                req.prefilled = 0
                mode = "recompute"
            if self.admission == "reserve":
                req.reserved_pages = self._worst_case_pages(req)
                self._reserved_blocks += req.reserved_pages
            self._count("restored")
            if self._observe:
                obs.get_recorder().instant(
                    "restored", cat="serve", track=self._req_track(req),
                    mode=mode, slot=slot)
                obs.get_metrics().gauge("serve.swap_blocks_in_use").set(
                    self.swap.in_use)

    def _first_token(self, req: Request):
        """Stamp + record the first-token milestone (TTFT)."""
        req.t_first = time.perf_counter()
        if self._observe:
            obs.get_recorder().instant("first_token", cat="serve",
                                       track=self._req_track(req))
            self._hist("serve.ttft_s").observe(req.t_first - req.t_enq)

    def _finish_slot(self, slot: int, status: Status = Status.OK,
                     reason: str = ""):
        req = self.slots[slot]
        req.t_done = time.perf_counter()
        if self._observe:
            rec = obs.get_recorder()
            t0 = req.t_first or req.t_admit or req.t_enq
            rec.complete("decode", rec.to_us(t0), rec.to_us(req.t_done),
                         cat="serve", track=self._req_track(req),
                         tokens=len(req.out))
            rec.instant("evicted", cat="serve", track=self._req_track(req))
            if status is Status.OK and req.t_first and len(req.out) > 1:
                self._hist("serve.tpot_s").observe(
                    (req.t_done - req.t_first) / (len(req.out) - 1))
        if status is Status.OK and req.t_admit:
            dt = req.t_done - req.t_admit
            self._avg_service_s = (dt if not self._avg_service_s
                                   else 0.8 * self._avg_service_s + 0.2 * dt)
        if req.reserved_pages:
            self._reserved_blocks -= req.reserved_pages
            req.reserved_pages = 0
        self.tables.release(slot)
        self.slots[slot] = None
        self.pos[slot] = 0
        self._last_logits.pop(slot, None)
        self._record_terminal(req, status, reason)
        if status is not Status.OK:
            self._count(status.value.lower())

    # -- device steps -------------------------------------------------------
    def _prefill_one_chunk(self, slot: int, stats: ServeStats, rec,
                           counts: dict | None = None) -> int:
        """Run one prefill chunk of ``slot``'s sequence; returns its
        tokens.  ``counts`` (traced) takes the chunk's expert counts."""
        req = self.slots[slot]
        C = self.prefill_chunk
        start = req.prefilled
        chunk = req.seq[start:start + C]
        n = len(chunk)
        track = self._req_track(req) if self._observe else "serve"
        # the chunk's host arrays and their transfers: a span of their
        # own, since ``prefill_chunk`` starts at the call
        with rec.span("prefill_build", cat="serve", track=track):
            toks = np.zeros((1, C), np.int32)
            toks[0, :n] = chunk
            batch = {"tokens": jnp.asarray(toks),
                     "block_tables": jnp.asarray(self.tables.row(slot)[None]),
                     "start": jnp.asarray(start, jnp.int32),
                     "length": jnp.asarray(n, jnp.int32),
                     "slot": jnp.asarray(slot, jnp.int32)}
        t0 = time.time()
        with rec.span("prefill_chunk", cat="serve", track=track, slot=slot,
                      start=start, tokens=n):
            with rec.span("prefill_dispatch", cat="serve", track=track):
                logits, self.cache = self._chunk(self.params, self.cache,
                                                 batch)
            with rec.span("prefill_wait", cat="serve", track=track):
                logits.block_until_ready()
                if counts is not None and "counts" in self.cache:
                    _add_expert_counts(counts,
                                       jax.device_get(self.cache["counts"]))
        stats.prefill_s += time.time() - t0
        req.prefilled += n
        self.pos[slot] = req.prefilled
        if req.prefilled >= len(req.seq):
            self._last_logits[slot] = logits[0]   # sample at next decode
        return n

    def _sample(self, logits):
        """logits: (V,) or (B, V) -> sampled token id(s), same leading
        shape.  With ``top_k``/``top_p`` set the fused Pallas sampling
        kernel filters + draws in one pass (DESIGN.md §13); otherwise the
        plain categorical / argmax path."""
        if self.temperature > 0 and (self.top_k is not None
                                     or self.top_p is not None):
            from repro.kernels.ops import sample_tokens
            rows = jnp.atleast_2d(logits)
            self._key, sub = jax.random.split(self._key)
            u = jax.random.uniform(sub, (rows.shape[0],))
            toks = sample_tokens(rows, u, temperature=self.temperature,
                                 top_k=self.top_k, top_p=self.top_p)
            return toks if logits.ndim > 1 else toks[0]
        if self.temperature > 0:
            self._key, sub = jax.random.split(self._key)
            return jax.random.categorical(sub, logits / self.temperature, -1)
        return jnp.argmax(logits, -1)

    def _check_poison(self, slot: int) -> bool:
        """True if the lane survived the chaos poison check; a poisoned
        request is isolated to a terminal ERROR with resources
        reclaimed — other lanes never see the fault."""
        if self.chaos is None:
            return True
        try:
            self.chaos.check_request(self.slots[slot].rid)
            return True
        except ChaosError as e:
            self._finish_slot(slot, Status.ERROR, str(e))
            return False

    def step(self, stats: ServeStats | None = None) -> int:
        """One engine step: expire deadlines, restore preempted lanes,
        admit, advance prefills, decode every running lane, retire
        finished requests.  Returns tokens emitted.

        Traced (``obs``), the step is one ``engine_step`` span on track
        ``serve`` holding one span per phase, in order: ``admit``,
        ``prefill_build`` then ``prefill_chunk`` (``prefill_dispatch``,
        ``prefill_wait``) per chunk, ``first_token``, ``batch_build``,
        ``decode_step`` (``decode_dispatch``, ``decode_sync``) and
        ``retire``.  Its args count the step's work from host integers,
        with no device sync: ``lanes`` decoded and ``prefill_tokens``.  A
        model with expert layers adds ``expert_rows`` (rows its held
        experts received over the step's programs and layers) and
        ``expert_rows_max`` (the busiest held expert's rows in one layer
        of one program), which the step's programs write beside the cache
        and the step reads at the syncs it already has."""
        stats = stats if stats is not None else ServeStats()
        rec = obs.get_recorder()
        with rec.span("engine_step", cat="serve", track="serve") as counts:
            if counts is not None:
                counts.update(lanes=0, prefill_tokens=0)
                if "counts" in self.cache:
                    counts.update(expert_rows=0, expert_rows_max=0)
            return self._step(stats, rec, counts)

    def _step(self, stats: ServeStats, rec, counts: dict | None) -> int:
        with rec.span("admit", cat="serve", track="serve"):
            if self.chaos is not None:
                self.chaos.on_admission()
            self._expire()
            self._restore_preempted()
            self._admit()

        budget = self.prefill_chunks_per_step
        for slot in range(self.max_batch):
            if budget <= 0:
                break
            req = self.slots[slot]
            if req is None or req.prefilled >= len(req.seq):
                continue
            if not self._check_poison(slot):
                continue
            target = min(req.prefilled + self.prefill_chunk, len(req.seq))
            if not self._ensure_blocks(slot, target):
                continue
            n = self._prefill_one_chunk(slot, stats, rec, counts)
            if counts is not None:
                counts["prefill_tokens"] += n
            budget -= 1

        if self._last_logits:
            with rec.span("first_token", cat="serve", track="serve"):
                self._sample_first_tokens()

        with rec.span("batch_build", cat="serve", track="serve"):
            lanes, batch = self._build_batch()
        if not lanes:
            return 0
        if self.chaos is not None:
            try:
                self.chaos.on_decode_step()
            except ChaosError:
                # transient device fault BEFORE dispatch: nothing was
                # mutated, so the identical step re-runs next iteration
                self._count("decode_faults")
                return 0
        if counts is not None:
            counts["lanes"] = len(lanes)
        if self._observe:
            rec.counter("blocks_in_use", self.alloc.in_use, track="serve",
                        cat="serve")
        t0 = time.time()
        with rec.span("decode_step", cat="serve", track="serve",
                      lanes=len(lanes)):
            with rec.span("decode_dispatch", cat="serve", track="serve"):
                logits, self.cache = self._decode(self.params, self.cache,
                                                  batch)
                sampled = self._sample(logits)
            with rec.span("decode_sync", cat="serve", track="serve"):
                if counts is not None and "counts" in self.cache:
                    nxt, got = jax.device_get((sampled, self.cache["counts"]))
                    _add_expert_counts(counts, got)
                else:
                    nxt = np.asarray(sampled)
        stats.decode_s += time.time() - t0
        stats.steps += 1

        with rec.span("retire", cat="serve", track="serve"):
            for b in lanes:
                req = self.slots[b]
                req.out.append(int(nxt[b]))
                self.pos[b] += 1
                stats.tokens_out += 1
                if req.done:
                    self._finish_slot(b)
        return len(lanes)

    def _sample_first_tokens(self):
        """Sample the first token of every lane whose prefill just
        completed (restored recompute lanes skip it: their next token is
        already in req.out, re-entering as the decode input)."""
        for slot, logits in list(self._last_logits.items()):
            req = self.slots[slot]
            if req.emit_first:
                req.out.append(int(np.asarray(self._sample(logits))))
                self._first_token(req)
            else:
                req.emit_first = True      # one skip per recompute restore
            del self._last_logits[slot]
            if req.done:                      # degenerate 1-token budget
                self._finish_slot(slot)

    def _build_batch(self):
        """The lanes that decode this step (their blocks ensured) and the
        decode batch for them; no lanes, no batch."""
        lanes = []
        for b, r in enumerate(self.slots):
            if r is None or r.prefilled < len(r.seq) or r.done:
                continue
            if not self._check_poison(b):
                continue
            # the incoming token is written at position pos[b]
            if not self._ensure_blocks(b, int(self.pos[b]) + 1):
                continue
            lanes.append(b)
        # a later lane's _ensure_blocks may have preempted an earlier
        # collected lane — drop lanes whose slot was emptied
        lanes = [b for b in lanes if self.slots[b] is not None]
        if not lanes:
            return lanes, None

        toks = np.zeros((self.max_batch, 1), np.int32)
        tables = np.zeros_like(self.tables.tables)
        pos = np.zeros(self.max_batch, np.int32)
        active = np.zeros(self.max_batch, bool)
        for b in lanes:
            req = self.slots[b]
            toks[b, 0] = req.out[-1]
            tables[b] = self.tables.row(b)
            pos[b] = self.pos[b]
            active[b] = True
        batch = {"tokens": jnp.asarray(toks),
                 "block_tables": jnp.asarray(tables),
                 "pos": jnp.asarray(pos),
                 "active": jnp.asarray(active)}
        return lanes, batch

    @property
    def busy(self) -> bool:
        return (bool(self.pending) or bool(self.preempted)
                or any(r is not None for r in self.slots))

    def run(self, stats: ServeStats | None = None,
            max_steps: int = 1_000_000) -> ServeStats:
        stats = stats if stats is not None else ServeStats()
        # report THIS run's high-water mark (in-flight blocks still count)
        self.alloc.peak_in_use = self.alloc.in_use
        # latency percentiles + lifecycle counts are computed over THIS
        # run's observations (registry/engine accumulate across runs)
        h_ttft = self._hist("serve.ttft_s")
        h_tpot = self._hist("serve.tpot_s")
        h_wait = self._hist("serve.queue_wait_s")
        marks = {id(h): len(h.values) for h in (h_ttft, h_tpot, h_wait)}
        counts0 = self._counts_mark
        done0 = self._results_mark
        steps = 0
        while self.busy:
            self.step(stats)
            steps += 1
            if steps > max_steps:
                stuck = ([r.rid for r in self.pending]
                         + [r.rid for r in self.slots if r is not None]
                         + [r.rid for r in self.preempted])
                raise ServeError(
                    f"engine did not drain the request queue in "
                    f"{max_steps} steps", stuck_rids=stuck,
                    blocks_in_use=self.alloc.in_use,
                    num_free=self.alloc.num_free)
        stats.peak_cache_blocks = self.alloc.peak_in_use
        from repro.core.memplan import kv_cache_bytes_paged
        stats.peak_cache_bytes = (self.alloc.peak_in_use
                                  * kv_cache_bytes_paged(
                                      self.cfg, [], self.block_size,
                                      kv_dtype=self.kv_dtype)
                                  ["block_bytes"])
        for name in ("preempted", "restored", "shed", "cancelled"):
            setattr(stats, name, self._counts[name] - counts0[name])
        stats.timeouts = self._counts["timeout"] - counts0["timeout"]
        stats.errors = self._counts["error"] - counts0["error"]
        stats.swap_peak_blocks = self.swap.peak_in_use
        stats.goodput_tokens = sum(
            max(0, len(res.tokens) - 1) for rid, res in self.results.items()
            if rid not in done0 and res.status is Status.OK)
        self._counts_mark = dict(self._counts)
        self._results_mark = set(self.results)

        def pcts(h):
            vs = h.values[marks[id(h)]:]
            return h.quantile(0.50, vs), h.quantile(0.99, vs)

        stats.ttft_p50, stats.ttft_p99 = pcts(h_ttft)
        stats.tpot_p50, stats.tpot_p99 = pcts(h_tpot)
        stats.queue_wait_p50, stats.queue_wait_p99 = pcts(h_wait)
        return stats

    def reset(self):
        """Drop all requests (unfinished ones are recorded CANCELLED) and
        recycle every block (cache contents stay — they are garbage by
        definition once unreferenced)."""
        for slot, r in enumerate(self.slots):
            if r is not None:
                self._finish_slot(slot)
        for req in self.pending:
            self._record_terminal(req, Status.CANCELLED, "engine reset")
        self.pending.clear()
        for req in self.preempted:
            if req.rid in self.swap:
                self.swap.pop(req.rid)
            self._record_terminal(req, Status.CANCELLED, "engine reset")
        self.preempted.clear()
        self.alloc = BlockAllocator(self.alloc.num_blocks, self.block_size,
                                    chaos=self.chaos)
        self.tables = BlockTables(self.alloc, self.max_batch, self.max_pages)
        self.swap = SwapPool(self.swap.capacity_blocks)
        self.pos[:] = 0
        self._reserved_blocks = 0

    def warmup(self) -> float:
        """Compile the chunk-prefill and decode steps (one throwaway
        request); returns the wall time (reported as ``compile_s``)."""
        t0 = time.time()
        saved_pending = self.pending
        self.pending = []
        saved_queue, self.max_queue = self.max_queue, None
        saved_chaos, self.chaos, self.alloc.chaos = self.chaos, None, None
        self._observe = False       # the throwaway request is not traffic
        try:
            # sized to fit even a tiny pool (one block of headroom)
            cap = (self.alloc.num_blocks - 2) * self.block_size
            n = max(1, min(self.prefill_chunk + 1, self.max_len - 2, cap))
            t = self.add_request([1] * n, 2)
            self.run()
            self.reset()
            # the throwaway is not traffic: scrub its terminal record so
            # callers tallying ``results`` only ever see real requests
            self.results.pop(t.rid, None)
            self._results_mark.discard(t.rid)
        finally:
            self._observe = True
            self.pending = saved_pending
            self.max_queue = saved_queue
            self.chaos = saved_chaos
            self.alloc.chaos = saved_chaos
        return time.time() - t0

    def generate(self, prompts: list[list[int]],
                 max_new_tokens: int | list[int] = 32,
                 temperature: float = 0.0, seed: int = 0,
                 top_k: int | None = None, top_p: float | None = None,
                 warmup: bool = True, priorities: list[int] | None = None,
                 deadlines_ms: list[float | None] | None = None):
        """Batch convenience API: enqueue everything, run to drain.

        Returns (list of per-request token lists, ServeStats) — requests
        may have different ``max_new_tokens`` (continuous batching's whole
        point), so the output is ragged.  A request that did not end
        ``OK`` (shed, timed out, errored) contributes the tokens it got
        to; consult ``engine.results[rid]`` for its typed status.
        """
        stats = ServeStats()
        if warmup:
            self.temperature = 0.0      # throwaway request decodes greedily
            stats.compile_s = self.warmup()
        # seed AFTER warmup so sampled streams are reproducible across
        # warmup settings
        self.temperature = temperature
        if top_k is not None:
            self.top_k = top_k
        if top_p is not None:
            self.top_p = top_p
        self._key = jax.random.PRNGKey(seed)
        budgets = (max_new_tokens if isinstance(max_new_tokens, (list, tuple))
                   else [max_new_tokens] * len(prompts))
        priorities = priorities or [0] * len(prompts)
        deadlines_ms = deadlines_ms or [None] * len(prompts)
        tickets = [self.add_request(p, n, priority=pr, deadline_ms=dl)
                   for p, n, pr, dl in zip(prompts, budgets, priorities,
                                           deadlines_ms)]
        self.run(stats)
        return [self.results[t.rid].tokens for t in tickets], stats
