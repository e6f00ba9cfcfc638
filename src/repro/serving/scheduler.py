"""Request scheduling: continuous batching + workload balancing.

Workload balancing (paper §5.2, C4 — TPU analogue): the paper balances
matmul rows across asymmetric big.LITTLE cores by their measured
throughput.  On a homogeneous pod the skew is in the *work*, not the
workers: variable-length requests.  ``balance_requests`` assigns requests
to data-parallel replica groups proportionally to per-replica rate weights
(and, with equal rates, equalizes total token load) — the same
"proportional split beats uniform split" insight, reproduced
quantitatively in benchmarks/bench_load_balance.py.

Continuous batching (``ContinuousScheduler``): per-slot admission for the
step-driven EngineLoop.  Requests join the decode batch the moment a slot
frees (prefill-on-join) instead of waiting for the whole batch to drain —
this kills the head-of-line blocking that makes slot-synchronous serving
lose throughput on mixed-length traffic.  Admission is FIFO with the
existing cost model as tie-break, bounded by slot and token budgets, with
optional preemption of the longest-running request under queue pressure.
"""
from __future__ import annotations

import dataclasses
import heapq
import math
from typing import TYPE_CHECKING, List, Optional, Sequence, Tuple

if TYPE_CHECKING:                      # sampling imports jax; keep this
    from repro.serving.sampling import SamplingParams  # pragma: no cover


class AdmissionError(ValueError):
    """The request can never be served by this loop configuration (prompt +
    decode budget exceed max_seq, the token budget, or the KV pool) — the
    serving gateway maps this to HTTP 400."""

    def __init__(self, message: str, uid: Optional[int] = None):
        super().__init__(message)
        self.uid = uid


class QueueFullError(AdmissionError):
    """The bounded submit queue is full — transient backpressure, retry
    later.  The serving gateway maps this to HTTP 429."""


@dataclasses.dataclass
class Request:
    uid: int
    prompt_tokens: List[int]
    max_new_tokens: int = 32
    adapter: Optional[str] = None      # multi-LoRA (C7)
    # per-request sampling (None until EngineLoop.submit resolves it
    # against the loop default); every request in a batch may carry its
    # own temperature/top-k/top-p/eos
    sampling: Optional["SamplingParams"] = None
    # QoS: higher priority admits first; deadline_s is an absolute
    # wall-clock deadline used for earliest-deadline-first ordering
    # within a priority class (None = no deadline)
    priority: int = 0
    deadline_s: Optional[float] = None
    # runtime state
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # continuous-batching runtime state (None/-1 until scheduled)
    slot: int = -1                     # decode-batch row currently held
    arrival_step: int = -1             # step the request entered the queue
    admit_step: int = -1               # step of (latest) admission
    finish_step: int = -1              # step the request completed
    preemptions: int = 0               # times evicted and requeued
    # pages a preempted request left Flash-resident: its resume allocates
    # DRAM only for the rest (cold pages stay on Flash and are staged on
    # demand), so admission must not charge them
    spilled_flash_pages: int = 0
    # mid-prefill spill victim awaiting resume: its restore reloads every
    # page byte-exact from Flash and adopts NOTHING from the prefix
    # index, so admission must charge the full prompt (no adoption
    # discount) or two same-step admissions could oversubscribe the pool
    resume_prefill: bool = False
    # per-request latency stats (wall-clock, filled by EngineLoop; the
    # gateway stamps arrival before it waits for the engine)
    arrival_t: float = 0.0
    admitted_t: float = 0.0            # first admission: the queue wait ends
    first_token_t: float = 0.0
    finish_t: float = 0.0

    @property
    def length(self) -> int:
        return len(self.prompt_tokens)

    @property
    def cost(self) -> float:
        """Approximate work: prefill tokens + expected decode steps."""
        return self.length + 4.0 * self.max_new_tokens

    @property
    def context_tokens(self) -> List[int]:
        """Tokens to (re)prefill on admission: prompt + anything already
        generated (non-empty after a preemption — resume re-prefills)."""
        return list(self.prompt_tokens) + list(self.generated)

    @property
    def decode_cap(self) -> int:
        """Effective decode budget: the request's own cap tightened by its
        sampling params (once resolved by submit)."""
        if self.sampling is not None:
            return min(self.max_new_tokens, self.sampling.max_new_tokens)
        return self.max_new_tokens

    @property
    def ttft(self) -> float:
        """Time to first token (s)."""
        return self.first_token_t - self.arrival_t

    @property
    def tpot(self) -> float:
        """Time per output token after the first (s)."""
        n = len(self.generated)
        if n <= 1:
            return 0.0
        return (self.finish_t - self.first_token_t) / (n - 1)


def balance_requests(requests: Sequence[Request], n_workers: int,
                     rates: Optional[Sequence[float]] = None
                     ) -> List[List[Request]]:
    """LPT-style proportional assignment (paper Fig. 4's 'balanced').

    rates: per-worker throughput weights (uniform when None) — the paper's
    per-core capability table; here, per-replica-group speed (useful with
    heterogeneous pod slices).
    """
    rates = list(rates) if rates else [1.0] * n_workers
    assert len(rates) == n_workers
    buckets: List[List[Request]] = [[] for _ in range(n_workers)]
    # min-heap on normalized finish time
    heap = [(0.0, i) for i in range(n_workers)]
    heapq.heapify(heap)
    for req in sorted(requests, key=lambda r: -r.cost):
        t, i = heapq.heappop(heap)
        buckets[i].append(req)
        heapq.heappush(heap, (t + req.cost / rates[i], i))
    return buckets


def uniform_requests(requests: Sequence[Request], n_workers: int
                     ) -> List[List[Request]]:
    """Round-robin (the paper's 'uniform' baseline)."""
    buckets: List[List[Request]] = [[] for _ in range(n_workers)]
    for j, req in enumerate(requests):
        buckets[j % n_workers].append(req)
    return buckets


def makespan(buckets: Sequence[Sequence[Request]],
             rates: Optional[Sequence[float]] = None) -> float:
    rates = list(rates) if rates else [1.0] * len(buckets)
    return max((sum(r.cost for r in b) / rate) if b else 0.0
               for b, rate in zip(buckets, rates))


# ===========================================================================
# Continuous batching
# ===========================================================================

class ContinuousScheduler:
    """Slot admission for the step-driven EngineLoop.

    * FIFO by arrival step; requests arriving on the same step are
      tie-broken by the C4 cost model (cheapest first — short requests
      drain slots faster, which is what continuous batching exploits).
    * Budgets: at most ``max_slots`` concurrent requests, and the committed
      token load (context + remaining decode budget, summed over running
      requests) never exceeds ``token_budget``.
    * Optional preemption: when a request has been waiting longer than
      ``preempt_patience`` steps with no slot free, the longest-running
      active request is evicted and requeued.  The engine spills the
      victim's KV pages to Flash and restores them page-exact on resume,
      so greedy decoding is unaffected.
    * Paged admission: with a ``pool`` (kv_pool.KVPoolManager), a request
      is admitted when the pages its *current* context actually needs are
      free — not when a worst-case max_seq reservation fits.  Growth
      beyond the free pool mid-decode is handled by page-pressure
      preemption in the engine (``evict``), which is what lets the same
      DRAM budget carry strictly more concurrent requests.
    """

    def __init__(self, max_slots: int, max_seq: int,
                 token_budget: Optional[int] = None,
                 preempt_patience: int = 0,
                 pool=None, spill_headroom=None):
        assert max_slots >= 1
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.token_budget = token_budget or max_slots * max_seq
        self.preempt_patience = preempt_patience
        self.pool = pool           # kv_pool.KVPoolManager (or None: dense)
        # proactive spill: callable -> pages the engine could free right
        # now by spilling cold pages of running rows to Flash (bounded by
        # the plan's Flash budget).  Admission may oversubscribe DRAM by
        # this much — the engine spills before it allocates.
        self.spill_headroom = spill_headroom
        self.waiting: List[Request] = []
        self.running: List[Optional[Request]] = [None] * max_slots
        self.step = 0

    # --- queue state -------------------------------------------------------
    @staticmethod
    def queue_key(r: Request):
        """Admission order: priority class first (higher admits earlier),
        earliest deadline within a class, then the original FIFO order
        with the C4 cost tie-break.  Requests with no deadline sort after
        every deadlined request of the same priority."""
        return (-r.priority,
                r.deadline_s if r.deadline_s is not None else math.inf,
                r.arrival_step, r.cost, r.uid)

    @property
    def active(self) -> List[Request]:
        return [r for r in self.running if r is not None]

    def has_work(self) -> bool:
        return bool(self.waiting) or any(r is not None for r in self.running)

    def _committed_tokens(self) -> int:
        return sum(len(r.context_tokens) + r.max_new_tokens -
                   len(r.generated) for r in self.active)

    def need_pages(self, req: Request) -> int:
        """Pages of *availability* the request consumes on admission: its
        context plus the first decode append — not the worst-case decode
        budget.  Fresh requests are charged only their non-shared pages:
        prefix pages another running row still holds (refcount >= 2) are
        adopted copy-free and cost the admission nothing.  Index-only
        pins stay charged — they sit inside ``available_pages``, and
        adoption makes them non-reclaimable.  A resumed request's pages
        still on Flash are not charged either: its restore allocates DRAM
        only for the rest."""
        need = self.pool.pages_for(len(req.context_tokens) + 1)
        if not req.generated:
            if not req.resume_prefill:
                need -= self.pool.probe_admission_discount(
                    req.prompt_tokens, salt=req.adapter or "")
        else:
            need -= req.spilled_flash_pages
        return max(need, 0)

    def _fits(self, req: Request, pending_pages: int = 0) -> bool:
        # legacy worst-case reservation (the explicit token_budget keeps
        # working — and is the baseline the paged accounting is measured
        # against in bench_continuous_batching)
        need = len(req.context_tokens) + req.max_new_tokens - len(req.generated)
        if self._committed_tokens() + need > self.token_budget:
            return False
        if self.pool is not None:
            # available = free list + evictable index pins (cached
            # prefixes are dropped before they ever block new work) +
            # cold pages of running rows the engine can spill to Flash
            # (admission oversubscribes DRAM up to the plan's Flash
            # budget; the engine spills before it allocates)
            avail = self.pool.available_pages - pending_pages
            if self.spill_headroom is not None:
                avail += self.spill_headroom()
            return self.need_pages(req) <= avail
        return True

    # --- transitions -------------------------------------------------------
    def submit(self, req: Request, arrival_step: Optional[int] = None) -> None:
        req.arrival_step = self.step if arrival_step is None else arrival_step
        self.waiting.append(req)

    def admit(self) -> List[Tuple[int, Request]]:
        """Fill free slots from the queue (FIFO, cost tie-break).  Returns
        the (slot, request) pairs admitted this step — the engine prefills
        each into its slot."""
        self.waiting.sort(key=self.queue_key)
        admitted: List[Tuple[int, Request]] = []
        pending_pages = 0
        for slot in range(self.max_slots):
            if self.running[slot] is not None or not self.waiting:
                continue
            cand = None
            for req in self.waiting:
                # remaining decode budget is max_new - generated: a resumed
                # request's generated tokens are already in context_tokens
                need = (len(req.context_tokens) + req.max_new_tokens
                        - len(req.generated))
                if need > self.max_seq:
                    continue        # can never run; don't block the queue
                if self._fits(req, pending_pages):
                    cand = req
                # strict queue order under the budget: a head that doesn't
                # fit *yet* blocks later arrivals (letting small requests
                # slip past would starve a large head indefinitely)
                break
            if cand is None:
                break
            self.waiting.remove(cand)
            cand.slot = slot
            cand.admit_step = self.step
            self.running[slot] = cand
            admitted.append((slot, cand))
            if self.pool is not None:
                # availability this admission will consume before the
                # engine actually allocates (multiple admissions per step)
                pending_pages += self.need_pages(cand)
        return admitted

    def evict(self, victim: Request) -> int:
        """Evict one running request and requeue it at the back of the
        FIFO (its early arrival step would otherwise win the very next
        admission and ping-pong).  Shared by patience preemption and the
        engine's page-pressure path.  Returns the freed slot."""
        freed = victim.slot
        self.running[freed] = None
        victim.slot = -1
        victim.preemptions += 1
        victim.arrival_step = self.step
        self.waiting.append(victim)
        return freed

    def maybe_preempt(self, exclude_slots: Optional[set] = None,
                      sampling_cap: Optional[int] = None
                      ) -> Optional[Tuple[int, Request]]:
        """Under queue pressure, evict the longest-running request (most
        generated tokens) so the head of the queue can make progress.
        At most one eviction per step; never evicts a request admitted this
        step, one about to finish (``sampling_cap`` tightens the per-request
        budget the engine actually decodes to), or one in ``exclude_slots``
        (the engine shields rows mid-resume-replay).
        Returns (freed_slot, victim)."""
        if not self.preempt_patience or not self.waiting:
            return None
        head = min(self.waiting, key=self.queue_key)
        if self.step - head.arrival_step < self.preempt_patience:
            return None
        if any(r is None for r in self.running):
            return None                      # a slot is free; no need
        # a victim must have held its slot >= patience steps: without this
        # minimum stint, a deep queue (every waiter past patience) would
        # trigger an eviction every step and each stint would net ~1 token
        # per re-prefill — pure thrash
        def cap(r: Request) -> int:
            c = r.decode_cap
            return min(c, sampling_cap) if sampling_cap is not None else c

        victims = [r for r in self.running
                   if r is not None
                   and (exclude_slots is None or r.slot not in exclude_slots)
                   and r.admit_step + self.preempt_patience <= self.step
                   and len(r.generated) >= 1
                   and len(r.generated) < cap(r) - 1]
        if not victims:
            return None
        # lowest priority class loses its slot first; within a class the
        # longest-running request (the original policy) is the victim,
        # and among equals the HIGHEST slot goes — admit() refills the
        # lowest free slot, so the active set stays dense in the low
        # slots and the engine's bucketed decode covers it with the
        # smallest possible batch bucket
        victim = max(victims,
                     key=lambda r: (-r.priority, len(r.generated), r.slot))
        return self.evict(victim), victim

    def finish(self, req: Request) -> None:
        req.done = True
        req.finish_step = self.step
        if req.slot >= 0:
            self.running[req.slot] = None
        req.slot = -1
