"""Continuous-batching inference engine — MLitB's "prediction to the
public at large" at framework scale (docs/serving.md).

The engine owns ONE preallocated KV buffer and interleaves prefill and
decode over it so requests of arbitrary prompt/generation length join
and leave mid-flight without retracing. The buffer comes in two
layouts: the classic DENSE slot cache of fixed ``(max_batch, max_seq)``
shape (the reference/oracle path), and the PAGED pool (``page_size``
set): fixed-size KV pages in one ``(n_layers, n_pages, n_kv,
page_size, head_dim)`` buffer with per-slot page lists on the host, so
memory scales with the tokens actually resident instead of
``max_batch * max_seq`` — plus cross-request PREFIX REUSE: a radix
trie keyed on (param version, prompt-token pages) lets requests sharing a prompt prefix prefill it
once and fork copy-on-write (shared pages are frozen — mapped
out-of-bounds in every write map — so a fork never copies and can never
mutate its parent's pages). See docs/serving.md §8.

  - **admission queue**: submitted requests wait FIFO until a slot frees;
  - **chunked prefill**: each engine step feeds every slot that still has
    prompt tokens pending one chunk of at most ``prompt_cap`` tokens,
    padded to a power-of-two ``(batch_cap, chunk_cap)`` bucket, and
    scatters the chunk's KV into the shared cache inside the same jitted
    fn — step fns are keyed on the bucket exactly like the reducer's
    capacity padding (core/reducer.py), so the trace cache is bounded by
    the number of DISTINCT buckets, not by request count (and prompts
    LONGER than the largest bucket simply take several steps);
  - **decode**: one fixed-shape ``(max_batch, max_seq)`` step over ALL
    slots with per-slot positions and a live mask — it traces exactly
    once, dead slots are masked out of the cache write, and finished
    requests free their slot for the next admission.

**Hot-swap** (the live train->serve loop, docs/serving.md §6):
``swap_params(params, version)`` atomically replaces the served model
WHILE requests are in flight. Every slot pins the version it was
admitted under and finishes its whole generation there; new admissions
use the latest version. The engine keeps a small ring of live param
trees — the pinned versions plus the latest — and runs one
prefill/decode dispatch per version present, so a swap never retraces
(the trees are trace-compatible by construction) and never corrupts an
in-flight request (each completion is bit-equal to a solo replay under
its pinned version; fuzzed in tests/test_train_serve.py). Versions
retire from the ring as their last pinned slot completes.

**Backpressure** (docs/robustness.md): ``max_queue`` bounds the
admission queue; overload sheds explicitly under ``shed_policy`` —
``"reject"`` refuses the newcomer, ``"drop_oldest"`` displaces the
stalest wait — and per-request admission deadlines shed queued requests
whose client has already given up. Every shed is recorded in
``shed_log`` (reason + clock); an admitted request always finishes.

**Sampling**: greedy by default (``temperature=0``), or temperature /
top-k sampling with a per-request PRNG key folded per generated token —
the key depends only on (engine seed, request id, token index), so a
request's stream is deterministic and independent of co-batching.

**Recurrent state beside pages** (docs/serving.md §11): a model with
``layer_types`` (Mamba2 and attention layers, Granite-4.0-H) keeps a
state pool by slot beside a KV pool that holds only its attention
layers. Admission zeroes the slot's state; each prefill chunk continues
it from where the previous chunk left it, and decode changes only the
rows it serves. It runs paged with the flash-decode kernel only, and
without prefix reuse or speculative decoding: pages alone cannot restore
a state, and a state cannot roll back rejected drafts.

Slot invariant: cache row ``s`` is valid exactly on ``[0, pos_s]`` and
decode at position ``p`` overwrites index ``p`` before attending to it,
so freed rows never need scrubbing and a slot's previous occupant can
never leak into its successor (tested in tests/test_serving.py).

Timing is pluggable: ``SimulatedServeSession`` drives the engine on a
discrete-event clock charged by a ``ServeCostModel`` over the PADDED
bucket shapes (what the accelerator actually pays) and accepts
timestamped arrivals AND timestamped param swaps, which is how
launch/train_serve.py threads one clock through training and serving;
``run_simulated`` wraps it for a closed schedule, ``run_closed_loop``
measures real wall-clock.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import (Any, Deque, Dict, List, Optional, Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import obs
from repro.kernels.flash_decode import pages_walked
from repro.models.layers import dtype_of
from repro.models.transformer import hybrid_cache, reset_slot_state
from repro.serving.config import (BackpressureConfig, PagingConfig,
                                  SamplingConfig, ServingConfig,
                                  SpeculativeConfig)
from repro.serving.paging import PagePool, PrefixTrie
from repro.train.step import build_draft_program, build_serve_programs

PyTree = Any

NEG_INF = -1e30
# Most rows one prefill-chunk dispatch of a layer_types model feeds, the
# oldest admissions first (the rest wait a step). A prefill chunk reads
# and writes its rows' state on top of the whole state pool, so its
# memory grows with the rows: at Granite-4.0-H-Micro's widths with 48
# slots the compiler puts a 4 x 512 chunk at 13.92 GB and 8 x 512 at
# 16.07 GB, more than a 16 GB chip holds. It also bounds the buckets.
HYBRID_PREFILL_ROWS = 4


def pow2_bucket(n: int, lo: int = 1, hi: Optional[int] = None) -> int:
    """Smallest power of two >= max(n, lo), clamped to ``hi`` (which the
    caller guarantees is itself >= n)."""
    b = max(1, int(lo))
    while b < n:
        b <<= 1
    return b if hi is None else min(b, int(hi))


@dataclass(frozen=True)
class ServeRequest:
    """One prediction request: an open-loop arrival from a client."""
    rid: int
    prompt: np.ndarray              # (P,) int32 prompt tokens
    max_new: int                    # tokens to generate
    arrival: float = 0.0            # open-loop arrival time (s)
    client_latency: float = 0.0     # one-way client network latency (s)
    deadline: Optional[float] = None  # max queue wait (s) before this
                                      # request sheds; None defers to the
                                      # engine's admission_deadline


@dataclass(frozen=True)
class Shed:
    """One load-shedding decision — the explicit record that a request
    was REFUSED rather than served (docs/robustness.md: sheds are part
    of the engine's output contract, never silently lost)."""
    rid: int
    reason: str                     # "queue_full" | "displaced" | "deadline"
    t: float = 0.0                  # clock at the shedding decision


@dataclass
class Completion:
    rid: int
    prompt_len: int
    tokens: np.ndarray              # (max_new,) int32 generated tokens
    finish: float = 0.0             # clock at completion (stamped by run_*)
    latency: float = 0.0            # finish - arrival + 2*client_latency
    version: int = 0                # param version the request was served
                                    # under (pinned at admission)
    admitted: Optional[float] = None     # the ``now`` of the step that
                                         # admitted it to a slot
    first_token: Optional[float] = None  # the ``now`` of the step whose
                                         # prefill sampled its first token
                                         # (None: the steps had no clock)


@dataclass
class StepReport:
    """What one engine step executed — the unit the cost model charges."""
    admitted: int
    prefill_shapes: List[Tuple[int, int]]       # (batch_cap, chunk_cap)*
    decode_dispatches: int                      # one per live version
    decode_batch: int                           # max_batch, or 0 if idle
    completed: List[Completion] = field(default_factory=list)
    shed: List[Shed] = field(default_factory=list)  # deadline sheds this step
    decode_pages: List[int] = field(default_factory=list)
    # ^ paged mode only: KV pages READ per decode dispatch (sum over the
    #   dispatch's live rows of pos//page_size + 1) — what a paged decode
    #   actually streams, so the cost model can charge per live page
    #   instead of per padded row
    decode_kv: List[int] = field(default_factory=list)
    # ^ paged mode and dense flash-decode: live KV tokens per decode
    #   dispatch (sum over its rows of pos + 1) — what the dense flash
    #   kernel's pos-bounded scan streams, so the cost model charges it
    #   per live token; in paged mode the count beside decode_pages
    verify_shapes: List[Tuple[int, int]] = field(default_factory=list)
    # ^ speculative mode: (batch, chunk_cap) of each verify dispatch —
    #   charged like a prefill chunk (it IS one); when non-empty there
    #   were NO plain decode dispatches this step
    draft_dispatches: int = 0           # speculative draft fn calls


@dataclass
class ServeStats:
    n_requests: int
    gen_tokens: int
    makespan: float
    tokens_per_s: float
    p50_latency: float
    p95_latency: float
    engine_steps: int
    prefill_tokens: int             # padded prefill tokens charged
    decode_rows_live: int           # live rows across all decode dispatches
    decode_rows_total: int          # max_batch * decode dispatches (padded)
    trace_count: int
    completions: List[Completion] = field(default_factory=list)
    prefill_chunks: int = 0         # chunk dispatches (== prefills when no
                                    # prompt exceeds prompt_cap)
    decode_dispatches: int = 0
    swap_count: int = 0             # param swaps applied during the run
    versions_served: Dict[int, int] = field(default_factory=dict)
    n_shed: int = 0                 # requests shed (never silently lost)
    queue_peak: int = 0             # deepest the admission queue got
    shed: List[Shed] = field(default_factory=list)
    concurrency_peak: int = 0       # most slots occupied at once (the
                                    # admitted-concurrency headline)
    pages_peak: int = 0             # paged: peak pages resident (slots+trie)
    prefix_hits: int = 0            # paged: admissions that reused pages
    reused_tokens: int = 0          # paged: prompt tokens NOT re-prefilled
    decode_kv_tokens: int = 0       # paged or dense flash: live KV tokens
                                    # across all decode dispatches
    decode_pages_live: int = 0      # paged: live KV pages across all
                                    # decode dispatches (sum decode_pages)
    decode_pages_walked: int = 0    # paged: KV pages the decode programs
                                    # visit, live or dead
    ttft_p50: Optional[float] = None   # time to first token, on the same
    ttft_p95: Optional[float] = None   # footing as latency (None: no clock)
    queue_wait_p95: Optional[float] = None  # arrival to admission, alike
    token_gap_mean: Optional[float] = None  # mean gap between a request's
                                            # tokens after its first
    spec_rounds: int = 0            # speculative: (row, verify) rounds run
    drafted: int = 0                # speculative: draft tokens proposed
    accepted: int = 0               # speculative: draft tokens accepted


@dataclass
class _SlotState:
    req: ServeRequest
    gen: List[int]
    ver: int                        # pinned param version
    filled: int = 0                 # prompt tokens prefilled so far
    pages: List[int] = field(default_factory=list)  # paged: ordered page ids
    n_shared: int = 0               # paged: leading pages read from the trie
    inserted: int = 0               # paged: prompt pages published so far
    admitted: Optional[float] = None     # clocks for the Completion
    first_token: Optional[float] = None
    seq: int = 0                    # admission order


class ServingEngine:
    """Admission queue + continuous batching over a shared slot KV cache,
    with in-flight param hot-swap and temperature/top-k sampling."""

    def __init__(self, params: PyTree, cfg: ArchConfig, *,
                 serving: ServingConfig):
        # grouped config is the ONLY entry point (docs/serving.md §1);
        # the flat-kwarg constructor completed its one deprecation cycle
        # and is gone — ``ServingConfig.from_flat(...)`` remains as the
        # kwargs-shaped builder for callers migrating mechanically
        self.hybrid = bool(cfg.layer_types)
        if cfg.arch_type not in ("dense", "moe") and not self.hybrid:
            raise ValueError(
                f"ServingEngine supports attention-cached LM archs "
                f"(dense/moe) and layer_types hybrids, not "
                f"{cfg.arch_type!r}")
        if self.hybrid:
            # the layout and the decode kernel are build_serve_programs'
            # to refuse; these two are the engine's
            if serving.paging is not None and serving.paging.prefix_reuse:
                raise ValueError(
                    f"{cfg.name}: prefix_reuse cannot serve recurrent "
                    f"(Mamba) layers: shared pages restore attention KV, "
                    f"not the state after the prefix; set "
                    f"PagingConfig(prefix_reuse=False)")
            if serving.speculative is not None:
                raise ValueError(
                    f"{cfg.name}: speculative decoding cannot serve "
                    f"recurrent (Mamba) layers: their state cannot roll "
                    f"back the rejected drafts")
        if cfg.sliding_window and serving.max_seq > cfg.sliding_window:
            raise ValueError(
                f"max_seq={serving.max_seq} exceeds sliding_window="
                f"{cfg.sliding_window}: the slot cache is linear (no ring)")
        if cfg.arch_type == "moe" and \
                cfg.moe.capacity_factor * cfg.moe.experts_per_token \
                < cfg.moe.n_experts:
            # per-row expert capacity ceil(S*k/E*cf) is computed from the
            # PADDED prefill length and the junk tail is routed too; only
            # cf >= E/k guarantees no row can overflow, so below that
            # ragged outputs may diverge from an unpadded run when
            # routing is skewed (models/transformer.py prefill docstring)
            import warnings
            warnings.warn(
                f"{cfg.name}: MoE capacity_factor="
                f"{cfg.moe.capacity_factor} can bind under padded ragged "
                f"prefill (needs >= n_experts/experts_per_token = "
                f"{cfg.moe.n_experts / cfg.moe.experts_per_token:.2f} for "
                f"exactness); outputs are approximate when an expert "
                f"overflows", stacklevel=2)
        self.cfg = cfg
        self.serving = serving
        self.max_batch = int(serving.max_batch)
        self.max_seq = int(serving.max_seq)
        self.prompt_bucket_min = int(serving.prompt_bucket_min)
        self.prompt_cap = serving.resolved_prompt_cap
        self.prefill_rows = HYBRID_PREFILL_ROWS if self.hybrid else None
        self._temperature = float(serving.sampling.temperature)
        self._top_k = int(serving.sampling.top_k)
        self._sample_seed = int(serving.sampling.sample_seed)
        self._unroll = serving.unroll
        self.decode_kernel = serving.decode_kernel
        self._spec = serving.speculative
        if self._spec is not None:
            dcfg = self._spec.draft_cfg
            if dcfg.arch_type not in ("dense", "moe"):
                raise ValueError(
                    f"speculative draft must be an attention LM "
                    f"(dense/moe), not {dcfg.arch_type!r}")
            if dcfg.vocab_size < cfg.vocab_size:
                raise ValueError(
                    f"speculative draft vocab_size={dcfg.vocab_size} "
                    f"cannot consume served tokens (vocab_size="
                    f"{cfg.vocab_size})")
        # the version ring: pinned live versions + the latest. A swap
        # installs a new latest; a version retires the moment its last
        # pinned slot completes (``_gc_versions`` runs from BOTH
        # ``swap_params`` and ``_finish``), so the ring never exceeds
        # max_batch + 1 trees and never waits for the next publish to
        # release a retired tree. ``start_version`` seeds the numbering
        # when the initial params come from a training checkpoint
        # (version == training step).
        self.version = int(serving.start_version)
        self._versions: Dict[int, PyTree] = {self.version: params}
        self.swap_count = 0
        # KV layout: dense slot cache (reference), or paged pool when
        # ``serving.paging`` is set (docs/serving.md §8). max_seq must
        # divide into whole pages so each row's gathered page view has
        # EXACTLY the dense row shape — that makes the inner prefill/
        # decode program identical and the paged engine bit-exact vs
        # dense (validated by ServingConfig at construction).
        self.paged = serving.paging is not None
        adt = dtype_of(cfg.activ_dtype)
        if self.paged:
            self.page_size = int(serving.paging.page_size)
            self.pages_per_slot = self.max_seq // self.page_size
            self.n_pages = int(serving.paging.n_pages) \
                if serving.paging.n_pages is not None \
                else self.max_batch * self.pages_per_slot
            self._pool: Optional[PagePool] = PagePool(self.n_pages,
                                                      self.page_size)
            self._trie: Optional[PrefixTrie] = PrefixTrie(self.page_size)
            self.prefix_reuse = bool(serving.paging.prefix_reuse)
            shape = (cfg.n_layers, self.n_pages, cfg.n_kv_heads,
                     self.page_size, cfg.head_dim)
            # pages one decode dispatch visits: the flash kernel's grid
            # (the XLA path's page gather reads the same rows x pages)
            self.pages_walked = pages_walked(self.max_batch,
                                             self.pages_per_slot)
        else:
            self.page_size = None
            self.pages_per_slot = 0
            self.n_pages = 0
            self.pages_walked = 0
            self._pool = None
            self._trie = None
            self.prefix_reuse = False
            shape = (cfg.n_layers, self.max_batch, self.max_seq,
                     cfg.n_kv_heads, cfg.head_dim)
        # every serving step program comes from the ONE factory — this is
        # the only place the engine touches repro.train.step
        self._programs = build_serve_programs(
            cfg, paged=self.paged, unroll=self._unroll,
            decode_kernel=self.decode_kernel)
        if self.hybrid:
            # the KV pool holds the attention layers only; the Mamba
            # layers' state lives in a pool by slot beside it
            self.cache: PyTree = hybrid_cache(cfg, self.max_batch,
                                              self.n_pages, self.page_size)
        else:
            self.cache = {"layers": {"k": jnp.zeros(shape, adt),
                                     "v": jnp.zeros(shape, adt)}}
        self._reset_fn = None
        self._admit_seq = 0
        self._slots: List[Optional[_SlotState]] = [None] * self.max_batch
        self._pos = np.zeros(self.max_batch, np.int32)
        self._tok = np.zeros(self.max_batch, np.int32)
        self._live = np.zeros(self.max_batch, bool)
        self._queue: Deque[ServeRequest] = deque()
        # backpressure (docs/robustness.md): bound the admission queue
        # and shed the overflow EXPLICITLY — a shed is an answer ("try
        # later"), a silently growing queue is a lie about capacity
        # (bounds validated by BackpressureConfig at construction)
        self.max_queue = serving.backpressure.max_queue
        self.shed_policy = serving.backpressure.shed_policy
        self.admission_deadline = serving.backpressure.admission_deadline
        self.shed_log: List[Shed] = []
        self.queue_peak = 0
        self._rids_active: set = set()  # queued or in-flight rids
        self._chunk_fns: Dict[Tuple[int, int], Any] = {}
        self._verify_fns: Dict[Tuple[int, int], Any] = {}
        self._decode_fn = None
        self._draft_fn = None
        self._trace_count = 0
        self.engine_steps = 0
        self.prefill_tokens = 0
        self.prefill_chunks = 0
        self.decode_dispatches = 0
        self.decode_rows_live = 0
        self.decode_rows_total = 0
        self.concurrency_peak = 0
        self.pages_peak = 0
        self.prefix_hits = 0
        self.reused_tokens = 0
        self.decode_kv_tokens = 0
        self.decode_pages_live = 0
        self.decode_pages_walked = 0
        self.spec_rounds = 0
        self.drafted = 0
        self.accepted = 0

    # ------------------------------------------------------------------
    @property
    def params(self) -> PyTree:
        """The LATEST param tree — what new admissions are served under."""
        return self._versions[self.version]

    @property
    def trace_count(self) -> int:
        """Number of ACTUAL jit traces taken (the counter increments
        inside the traced python body, so cache hits don't count). The
        property test bounds this by distinct buckets, not requests —
        and a hot-swap must not move it at all."""
        return self._trace_count

    @property
    def n_live(self) -> int:
        return int(self._live.sum())

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def buckets_seen(self) -> List[Tuple[int, int]]:
        return sorted(self._chunk_fns)

    @property
    def verify_buckets_seen(self) -> List[Tuple[int, int]]:
        """Speculative mode: (batch, chunk_cap) buckets the verify
        dispatch has traced — bounded by ONE per engine (the cap is
        pinned to pow2_bucket(k + 1)), which is the '+ verify buckets'
        allowance in the trace invariant."""
        return sorted(self._verify_fns)

    @property
    def live_versions(self) -> List[int]:
        """Versions currently held in the ring (pinned and/or latest)."""
        return sorted(self._versions)

    @property
    def pages_free(self) -> int:
        """Paged mode: pages not held by any slot or the prefix trie."""
        return self._pool.n_free if self.paged else 0

    @property
    def trie_pages(self) -> int:
        """Paged mode: pages held (only) as reusable prefix KV."""
        return self._trie.n_pages_held if self.paged else 0

    def _pages_needed(self, prompt_len: int, max_new: int) -> int:
        # positions ever WRITTEN: prompt [0, plen) by chunks, then decode
        # at plen .. plen+max_new-2 (the last sampled token is returned,
        # never cached) -> plen + max_new - 1 slots of KV
        return -(-(prompt_len + max_new - 1) // self.page_size)

    def flush_prefix_cache(self) -> int:
        """Drop every trie-held page (all versions); returns pages
        released. Slot-held pages are untouched — in-flight requests
        keep reading the prefixes they forked from."""
        return self._trie.drop_all(self._pool) if self.paged else 0

    # ------------------------------------------------------------------
    def submit(self, req: ServeRequest, now: Optional[float] = None) -> bool:
        """Enqueue ``req``. Returns True when admitted to the queue,
        False when shed by backpressure (the shed is recorded in
        ``shed_log`` — refusals are reported, never silent). ``now`` is
        the submitting clock and stamps any shed this call causes; when
        omitted it defaults to ``req.arrival`` — NOT zero — so shed
        timestamps stay monotone with the schedule even for callers
        without a clock (tests/test_backpressure.py). A duplicate rid
        (already queued or in flight) is a protocol error — it would
        corrupt completion bookkeeping AND the sampling key stream (keys
        fold in the rid) — and raises ``ValueError``."""
        t = float(now) if now is not None else float(req.arrival)
        p = int(np.asarray(req.prompt).size)
        if p < 1 or req.max_new < 1:
            raise ValueError(f"request {req.rid}: empty prompt or max_new")
        if p + req.max_new > self.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt({p}) + max_new({req.max_new}) "
                f"exceeds max_seq={self.max_seq}")
        if self.paged and self._pages_needed(p, req.max_new) > self.n_pages:
            raise ValueError(
                f"request {req.rid}: needs "
                f"{self._pages_needed(p, req.max_new)} pages, pool has "
                f"{self.n_pages} — can never be admitted")
        if req.rid in self._rids_active:
            raise ValueError(
                f"request {req.rid}: duplicate rid already queued or in "
                f"flight")
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            if self.shed_policy == "reject":
                self.shed_log.append(Shed(req.rid, "queue_full", t))
                return False
            victim = self._queue.popleft()       # drop_oldest: the victim
            self._rids_active.discard(victim.rid)  # is the stalest wait
            self.shed_log.append(Shed(victim.rid, "displaced", t))
        self._queue.append(req)
        self._rids_active.add(req.rid)
        self.queue_peak = max(self.queue_peak, len(self._queue))
        return True

    # ------------------------------------------------------------------
    def swap_params(self, params: PyTree, version: Optional[int] = None
                    ) -> int:
        """Atomically install ``params`` as the latest served version,
        while requests are in flight: slots already admitted keep
        decoding under the version they pinned at admission; every
        admission from now on uses the new tree. The tree must be
        TRACE-COMPATIBLE with the current one (same structure, leaf
        shapes and dtypes) — that is what makes the swap free of
        retraces. Returns the installed version number."""
        cur = self._versions[self.version]
        if jax.tree.structure(params) != jax.tree.structure(cur):
            raise ValueError(
                "swap_params: tree structure differs from the served "
                "model — not trace-compatible")
        for new, old in zip(jax.tree.leaves(params), jax.tree.leaves(cur)):
            if (jnp.shape(new) != jnp.shape(old)
                    or jnp.asarray(new).dtype != jnp.asarray(old).dtype):
                raise ValueError(
                    f"swap_params: leaf {jnp.shape(new)}/"
                    f"{jnp.asarray(new).dtype} differs from served "
                    f"{jnp.shape(old)}/{jnp.asarray(old).dtype} — not "
                    f"trace-compatible")
        if version is None:
            version = self.version + 1
        if version <= self.version:
            raise ValueError(f"swap_params: version {version} must exceed "
                             f"the current latest {self.version}")
        self._versions[int(version)] = params
        self.version = int(version)
        self.swap_count += 1
        self._gc_versions()
        return self.version

    def _gc_versions(self) -> None:
        """Retire ring versions with no pinned slot (runs on every swap
        AND every slot completion — a dead tree is released immediately,
        never held until the next publish). In paged mode a retired
        version also drops its whole prefix-trie generation: KV pages
        are only valid under the version that wrote them, and once the
        ring retires ``v`` no future admission can pin ``v`` again."""
        pinned = {st.ver for st in self._slots if st is not None}
        pinned.add(self.version)
        for v in [v for v in self._versions if v not in pinned]:
            del self._versions[v]
            if self.paged:
                self._trie.drop_version(v, self._pool)

    # ------------------------------------------------------------------
    def _sample(self, logits: jnp.ndarray, rids: jnp.ndarray,
                gidx: jnp.ndarray) -> jnp.ndarray:
        """Traced next-token choice over (B,V) logits. ``temperature=0``
        is EXACTLY the greedy argmax (the oracle-pinned path); otherwise
        each row draws from its own PRNG key, folded from (engine seed,
        request id, generated-token index) — never from slot or co-batch
        state, so streams replay identically solo vs co-batched."""
        if self._temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lg = logits.astype(jnp.float32) / self._temperature
        if self._top_k > 0 and self._top_k < lg.shape[-1]:
            # keep EXACTLY k candidates by scattering top_k's own picks:
            # masking with ``lg < kth`` would keep every logit TIED with
            # the k-th and silently widen the support past k. top_k
            # breaks ties by lowest index (stable descending sort), so
            # the kept set is deterministic and top_k=1 is greedy-equal
            # even when the argmax value repeats.
            vals, idx = jax.lax.top_k(lg, self._top_k)
            rows = jnp.arange(lg.shape[0], dtype=jnp.int32)[:, None]
            lg = jnp.full_like(lg, NEG_INF).at[rows, idx].set(vals)
        base = jax.random.PRNGKey(self._sample_seed)

        def draw(rid, g, row):
            key = jax.random.fold_in(jax.random.fold_in(base, rid), g)
            return jax.random.categorical(key, row)
        return jax.vmap(draw)(rids, gidx, lg).astype(jnp.int32)

    # ------------------------------------------------------------------
    def _get_chunk_fn(self, bcap: int, ccap: int):
        fn = self._chunk_fns.get((bcap, ccap))
        if fn is not None:
            return fn
        if self.paged:
            pstep = self._programs.prefill_chunk

            def chunk_paged(params, tokens, off, clen, rids, rmap, wmap,
                            slots, pool):
                self._trace_count += 1      # trace-time only side effect
                logits, pool = pstep(params, tokens, off, clen, pool,
                                     rmap, wmap, slots)
                nxt = self._sample(logits[:, -1, :], rids,
                                   jnp.zeros_like(rids))
                return nxt, pool

            fn = jax.jit(chunk_paged, donate_argnums=(8,))
            self._chunk_fns[(bcap, ccap)] = fn
            return fn
        cstep = self._programs.prefill_chunk
        last = self.max_batch - 1

        def chunk_and_scatter(params, tokens, off, clen, slots, rids,
                              cache):
            self._trace_count += 1          # trace-time only side effect
            # gather the group's slot rows; padding rows carry slot ==
            # max_batch — clip for the gather (junk is fine, their
            # outputs are dropped), keep OOB for the scatter (dropped)
            rows = jax.tree.map(lambda c: c[:, jnp.clip(slots, 0, last)],
                                cache)
            logits, rows = cstep(params, tokens, off, clen, rows)
            nxt = self._sample(logits[:, -1, :], rids,
                               jnp.zeros_like(rids))
            new = {}
            for name in ("k", "v"):
                buf = cache["layers"][name]
                upd = rows["layers"][name].astype(buf.dtype)
                new[name] = buf.at[:, slots].set(upd)
            return nxt, {"layers": new}

        # donate the cache: step() overwrites self.cache with the return
        # value, so aliasing in-place avoids copying the full slot
        # buffers (the dominant memory traffic) every engine step
        fn = jax.jit(chunk_and_scatter, donate_argnums=(6,))
        self._chunk_fns[(bcap, ccap)] = fn
        return fn

    def _get_decode_fn(self):
        if self._decode_fn is not None:
            return self._decode_fn
        if self.paged:
            pstep = self._programs.decode

            def decode_paged(params, tok, pos, live, pool, rids, gidx,
                             rmap, wmap):
                self._trace_count += 1
                logits, pool = pstep(params, tok, pos, pool, live, rmap,
                                     wmap)
                nxt = self._sample(logits[:, -1, :], rids, gidx)
                return nxt, pool

            self._decode_fn = jax.jit(decode_paged, donate_argnums=(4,))
            return self._decode_fn
        dstep = self._programs.decode

        def decode_all_slots(params, tok, pos, live, cache, rids, gidx):
            self._trace_count += 1
            logits, cache = dstep(params, tok, pos, cache, live)
            nxt = self._sample(logits[:, -1, :], rids, gidx)
            return nxt, cache

        self._decode_fn = jax.jit(decode_all_slots, donate_argnums=(4,))
        return self._decode_fn

    def _reset_states(self, slots: List[int]) -> None:
        """Zero the recurrent state of the newly admitted ``slots`` (one
        fixed-shape dispatch, padded with out-of-pool slots)."""
        if self._reset_fn is None:
            def reset(state, idx):
                self._trace_count += 1      # trace-time only side effect
                return reset_slot_state(state, idx)
            self._reset_fn = jax.jit(reset, donate_argnums=(0,))
        idx = np.full(self.max_batch, self.max_batch, np.int32)
        idx[:len(slots)] = slots
        self.cache = dict(self.cache, state=self._reset_fn(
            self.cache["state"], jnp.asarray(idx)))

    def _get_verify_fn(self, vcap: int):
        """Speculative VERIFY dispatch for one ``(max_batch, vcap)``
        bucket: a prefill-chunk-shaped program over ALL slots (row ==
        slot, so no gather) returning the GREEDY argmax at every chunk
        column. Rows outside the dispatch's version group carry
        ``clen == 0`` — no write, output discarded — the same padding
        convention as prefill chunks. Greedy-only by construction
        (ServingConfig rejects speculative + temperature > 0)."""
        key = (self.max_batch, vcap)
        fn = self._verify_fns.get(key)
        if fn is not None:
            return fn
        vstep = self._programs.verify
        if self.paged:
            def verify_paged(params, tokens, off, clen, rmap, wmap, pool):
                self._trace_count += 1  # trace-time only side effect
                logits, pool = vstep(params, tokens, off, clen, pool,
                                     rmap, wmap)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool

            fn = jax.jit(verify_paged, donate_argnums=(6,))
        else:
            def verify_dense(params, tokens, off, clen, cache):
                self._trace_count += 1  # trace-time only side effect
                logits, cache = vstep(params, tokens, off, clen, cache)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

            fn = jax.jit(verify_dense, donate_argnums=(4,))
        self._verify_fns[key] = fn
        return fn

    def _get_draft_fn(self):
        """The speculative DRAFT dispatch: one jitted k-proposal program
        over all live rows at once (the draft tree is engine-fixed, so
        there is never a per-version split)."""
        if self._draft_fn is not None:
            return self._draft_fn
        spec = self._spec
        dstep = build_draft_program(spec.draft_cfg, k=spec.k,
                                    window=spec.window)

        def draft(params, window_toks, hlen):
            self._trace_count += 1      # trace-time only side effect
            return dstep(params, window_toks, hlen)

        self._draft_fn = jax.jit(draft)
        return self._draft_fn

    # ------------------------------------------------------------------
    def _finish(self, s: int) -> Completion:
        st = self._slots[s]
        self._slots[s] = None
        self._live[s] = False
        self._pos[s] = 0
        self._tok[s] = 0
        self._rids_active.discard(st.req.rid)
        if self.paged:
            for p in st.pages:          # drop the slot's reference; pages
                self._pool.decref(p)    # the trie published stay resident
        self._gc_versions()
        return Completion(rid=st.req.rid, prompt_len=len(st.req.prompt),
                          tokens=np.asarray(st.gen, np.int32),
                          version=st.ver, admitted=st.admitted,
                          first_token=st.first_token)

    # -- paged-mode host bookkeeping -----------------------------------
    def _plan_pages(self, req: ServeRequest
                    ) -> Optional[Tuple[List[int], int]]:
        """Admission-time page plan for ``req``: the longest reusable
        prefix run from the trie (under the CURRENT version — what this
        admission pins) plus freshly allocated pages for everything it
        will write, evicting idle trie pages if the free list runs
        short. Returns ``(pages, n_shared)`` or None when the pool
        cannot satisfy the request yet (the caller stops admitting:
        strict FIFO, the head of the line waits for pages). All-or-
        nothing — a request never holds a partial allocation."""
        plen = len(req.prompt)
        shared: List[int] = []
        if self.prefix_reuse:
            # never reuse the page holding the prompt's LAST token: at
            # least one real token must go through prefill so the final
            # chunk's logits produce the first sampled token
            shared = self._trie.lookup(self.version, req.prompt,
                                       (plen - 1) // self.page_size)
        for p in shared:                # pin before any eviction could
            self._pool.incref(p)        # reap a ref==1 trie page
        own_need = self._pages_needed(plen, req.max_new) - len(shared)
        own = self._pool.alloc(own_need)
        if own is None:
            self._trie.evict_idle(self._pool, own_need - self._pool.n_free)
            own = self._pool.alloc(own_need)
        if own is None:
            for p in shared:
                self._pool.decref(p)
            return None
        if shared:
            self.prefix_hits += 1
            self.reused_tokens += len(shared) * self.page_size
        return shared + own, len(shared)

    def _chunk_page_maps(self, group: List[int], bcap: int
                         ) -> Tuple[np.ndarray, np.ndarray]:
        """(read map, write map) for one prefill-chunk dispatch: row i of
        the bucket maps slot ``group[i]``'s pages in order; every other
        entry is OOB (== n_pages). The write map additionally OOBs
        FROZEN pages — shared prefixes are read-only by construction."""
        rmap = np.full((bcap, self.pages_per_slot), self.n_pages, np.int32)
        wmap = np.full((bcap, self.pages_per_slot), self.n_pages, np.int32)
        for i, s in enumerate(group):
            st = self._slots[s]
            for j, p in enumerate(st.pages):
                rmap[i, j] = p
                if not self._pool.frozen[p]:
                    wmap[i, j] = p
        return rmap, wmap

    def _decode_page_maps(self, group: np.ndarray
                          ) -> Tuple[np.ndarray, np.ndarray]:
        """(read map, write map) over ALL slots for one decode dispatch;
        only rows in ``group`` (this dispatch's version) may write."""
        rmap = np.full((self.max_batch, self.pages_per_slot), self.n_pages,
                       np.int32)
        wmap = np.full((self.max_batch, self.pages_per_slot), self.n_pages,
                       np.int32)
        for s in range(self.max_batch):
            st = self._slots[s]
            if st is None:
                continue
            for j, p in enumerate(st.pages):
                rmap[s, j] = p
                if group[s] and not self._pool.frozen[p]:
                    wmap[s, j] = p
        return rmap, wmap

    def _publish_prompt_pages(self, st: _SlotState) -> None:
        """Offer the slot's COMPLETED prompt pages to the prefix trie
        (under the slot's pinned version — that's the tree the KV was
        computed with). A published page is increfed by the trie and
        FROZEN: it leaves every future write map, so later forks read it
        copy-on-write. If an identical prompt raced us in, our copy just
        stays private (refused insert)."""
        plen = len(st.req.prompt)
        n_done = min(st.filled // self.page_size, plen // self.page_size)
        while st.inserted < n_done:
            j = st.inserted
            page = st.pages[j]
            if self._trie.insert(st.ver, st.req.prompt, j, page):
                self._pool.incref(page)
                self._pool.frozen[page] = True
            st.inserted += 1

    def _run_chunks(self, completed: List[Completion],
                    now: Optional[float] = None) -> List[Tuple[int, int]]:
        """Feed one <=prompt_cap chunk to every slot with prompt tokens
        pending, one bucketed dispatch per pinned version present. A
        slot whose prompt completes samples its first token (stamped
        with ``now``) and goes live (decodable this same step)."""
        shapes: List[Tuple[int, int]] = []
        todo = [s for s in range(self.max_batch)
                if self._slots[s] is not None
                and self._slots[s].filled < len(self._slots[s].req.prompt)]
        for ver in sorted({self._slots[s].ver for s in todo}):
            group = [s for s in todo if self._slots[s].ver == ver]
            if self.prefill_rows is not None:
                group = sorted(group, key=lambda s: self._slots[s].seq)[
                    :self.prefill_rows]
            with obs.span("serve.prefill") as sp:
                if obs.tracing() and self.hybrid:
                    # rows whose chunk continues a carried state
                    sp.set_metadata(state_carried=sum(
                        self._slots[s].filled > 0 for s in group))
                (bcap, ccap), n_first, n_tokens = self._run_chunk(
                    group, ver, completed, now)
                shapes.append((bcap, ccap))
                if obs.tracing():
                    sp.set_metadata(batch=bcap, chunk=ccap, tokens=n_tokens,
                                    first_tokens=n_first)
        return shapes

    def _run_chunk(self, group: List[int], ver: int,
                   completed: List[Completion], now: Optional[float]
                   ) -> Tuple[Tuple[int, int], int, int]:
        """One chunk dispatch over the slots ``group`` (all pinned to
        ``ver``): build its arrays, run it, read back the sampled
        tokens, advance the slots. Returns its (batch, chunk) bucket,
        the prompts it completed and the prompt tokens it fed."""
        clens = [min(len(self._slots[s].req.prompt)
                     - self._slots[s].filled, self.prompt_cap)
                 for s in group]
        bcap = pow2_bucket(len(group))
        ccap = pow2_bucket(max(clens), lo=self.prompt_bucket_min,
                           hi=self.prompt_cap)
        tokens = np.zeros((bcap, ccap), np.int32)
        off = np.zeros(bcap, np.int32)
        cl = np.zeros(bcap, np.int32)
        slots = np.full(bcap, self.max_batch, np.int32)
        rids = np.zeros(bcap, np.int32)
        for i, s in enumerate(group):
            st = self._slots[s]
            tokens[i, :clens[i]] = \
                st.req.prompt[st.filled:st.filled + clens[i]]
            off[i] = st.filled
            cl[i] = clens[i]
            slots[i] = s
            rids[i] = st.req.rid % (2 ** 31)
        fn = self._get_chunk_fn(bcap, ccap)
        if self.paged:
            rmap, wmap = self._chunk_page_maps(group, bcap)
            nxt, self.cache = fn(self._versions[ver],
                                 jnp.asarray(tokens), jnp.asarray(off),
                                 jnp.asarray(cl), jnp.asarray(rids),
                                 jnp.asarray(rmap), jnp.asarray(wmap),
                                 jnp.asarray(slots), self.cache)
        else:
            nxt, self.cache = fn(self._versions[ver],
                                 jnp.asarray(tokens),
                                 jnp.asarray(off), jnp.asarray(cl),
                                 jnp.asarray(slots), jnp.asarray(rids),
                                 self.cache)
        with obs.span("serve.readback"):
            nxt = np.asarray(nxt)
        self.prefill_tokens += bcap * ccap
        self.prefill_chunks += 1
        n_first = 0
        for i, s in enumerate(group):
            st = self._slots[s]
            st.filled += clens[i]
            self._pos[s] = st.filled
            if self.paged and self.prefix_reuse:
                self._publish_prompt_pages(st)
            if st.filled == len(st.req.prompt):
                n_first += 1
                st.gen = [int(nxt[i])]
                st.first_token = now
                self._tok[s] = int(nxt[i])
                self._live[s] = True
                if st.req.max_new <= 1:
                    completed.append(self._finish(s))
        return (bcap, ccap), n_first, sum(clens)

    def step(self, now: Optional[float] = None) -> StepReport:
        """One engine iteration: admit waiting requests into free slots,
        run one prefill chunk for every slot with prompt pending
        (bucketed, grouped by pinned version), then one decode dispatch
        per live version across all slots. Returns what ran, for the
        cost model to charge.

        When the caller supplies ``now``, queued requests whose wait has
        exceeded their admission deadline (``req.deadline``, else the
        engine's ``admission_deadline``) are shed BEFORE admission — a
        stale request must not occupy a slot for a client that has
        already given up. In-flight requests never shed: an admitted
        request always finishes. ``now`` also stamps each request's
        ``admitted`` and ``first_token`` clocks.

        Spans (``repro.core.obs``), nested as listed:
        ``mlitb.serve.step`` (args ``queued``, ``live_rows``) holds
        ``mlitb.serve.admit`` (``admitted``, ``shed``, and with ``now``
        ``queue_wait_max_s``), one ``mlitb.serve.prefill`` per chunk
        dispatch (``batch``, ``chunk`` — the bucket — ``tokens``,
        ``first_tokens``) and one ``mlitb.serve.decode`` per decode or
        verify dispatch (``rows_live``, ``kv_tokens_live`` and, paged,
        ``kv_pages_live``, ``kv_pages_walked``); each dispatch span holds
        ``mlitb.serve.readback``, where the host waits for the sampled
        tokens. A ``layer_types`` model adds ``state_resets`` (slots
        whose state admission zeroed) to the admit span,
        ``state_carried`` (rows whose chunk starts from a carried state)
        to the prefill span, and ``state_rows_live`` / ``state_rows_walked``
        (rows whose state the decode program reads and writes) to the
        decode span."""
        with obs.span("serve.step", queued=len(self._queue)) as sp:
            if obs.tracing():
                sp.set_metadata(live_rows=self.n_live)
            completed: List[Completion] = []
            shed: List[Shed] = []
            admitted = self._admit(now, shed)
            prefill_shapes = self._run_chunks(completed, now)

            dispatches = 0
            decode_pages: List[int] = []
            decode_kv: List[int] = []
            verify_shapes: List[Tuple[int, int]] = []
            draft_dispatches = 0
            if self._live.any():
                if self._spec is not None:
                    dispatches, draft_dispatches = self._step_speculative(
                        completed, verify_shapes)
                else:
                    dispatches = self._step_decode(completed, decode_pages,
                                                   decode_kv)
        self.engine_steps += 1
        return StepReport(admitted, prefill_shapes, dispatches,
                          self.max_batch if dispatches else 0, completed,
                          shed, decode_pages, decode_kv, verify_shapes,
                          draft_dispatches)

    def _admit(self, now: Optional[float], shed: List[Shed]) -> int:
        """Deadline sheds (with ``now``), then FIFO admission into free
        slots with page planning. Appends the sheds to ``shed``; returns
        how many requests were admitted."""
        with obs.span("serve.admit") as sp:
            if now is not None and self._queue:
                kept: Deque[ServeRequest] = deque()
                for req in self._queue:
                    dl = req.deadline if req.deadline is not None \
                        else self.admission_deadline
                    if dl is not None and now - req.arrival > dl:
                        self._rids_active.discard(req.rid)
                        s = Shed(req.rid, "deadline", float(now))
                        self.shed_log.append(s)
                        shed.append(s)
                    else:
                        kept.append(req)
                self._queue = kept
            free = [s for s in range(self.max_batch)
                    if self._slots[s] is None]
            admitted = 0
            taken: List[int] = []
            wait_max = None
            while self._queue and free:
                req = self._queue[0]
                if self.paged:
                    plan = self._plan_pages(req)
                    if plan is None:
                        break           # strict FIFO: the head of the
                                        # line waits for pages to free
                    pages, n_shared = plan
                    reused = n_shared * self.page_size
                else:
                    pages, n_shared, reused = [], 0, 0
                self._queue.popleft()
                s = free.pop(0)
                self._slots[s] = _SlotState(
                    req=req, gen=[], ver=self.version, filled=reused,
                    pages=pages, n_shared=n_shared, inserted=n_shared,
                    admitted=now, seq=self._admit_seq)
                self._admit_seq += 1
                self._pos[s] = reused
                self._live[s] = False
                admitted += 1
                taken.append(s)
                if now is not None:
                    wait = now - req.arrival
                    wait_max = wait if wait_max is None \
                        else max(wait_max, wait)
            self.concurrency_peak = max(
                self.concurrency_peak,
                sum(1 for st in self._slots if st is not None))
            if self.paged:
                self.pages_peak = max(self.pages_peak, self._pool.n_used)
            if self.hybrid and taken:
                self._reset_states(taken)
            if obs.tracing():
                sp.set_metadata(admitted=admitted, shed=len(shed))
                if self.hybrid:
                    sp.set_metadata(state_resets=len(taken))
                if wait_max is not None:
                    sp.set_metadata(queue_wait_max_s=float(wait_max))
        return admitted

    def _step_decode(self, completed: List[Completion],
                     decode_pages: List[int], decode_kv: List[int]) -> int:
        """Plain decode: ONE fixed-shape ragged dispatch per live
        version, each row advancing exactly one token."""
        dispatches = 0
        fn = self._get_decode_fn()
        rids = np.zeros(self.max_batch, np.int32)
        gidx = np.zeros(self.max_batch, np.int32)
        for s in range(self.max_batch):
            if self._live[s]:
                rids[s] = self._slots[s].req.rid % (2 ** 31)
                gidx[s] = len(self._slots[s].gen)
        vers = sorted({self._slots[s].ver
                       for s in range(self.max_batch) if self._live[s]})
        for ver in vers:
            with obs.span("serve.decode") as sp:
                group = np.array([self._live[s]
                                  and self._slots[s].ver == ver
                                  for s in range(self.max_batch)], bool)
                args = self._decode_args(self._pos[group])
                if self.paged or self.decode_kernel == "flash":
                    # all the dense flash kernel's pos-bounded scan
                    # streams, for the cost model
                    decode_kv.append(args["kv_tokens_live"])
                    self.decode_kv_tokens += args["kv_tokens_live"]
                if self.paged:
                    rmap, wmap = self._decode_page_maps(group)
                    decode_pages.append(args["kv_pages_live"])
                    self.decode_pages_live += args["kv_pages_live"]
                    self.decode_pages_walked += self.pages_walked
                    nxt, self.cache = fn(self._versions[ver],
                                         jnp.asarray(self._tok[:, None]),
                                         jnp.asarray(self._pos),
                                         jnp.asarray(group), self.cache,
                                         jnp.asarray(rids),
                                         jnp.asarray(gidx),
                                         jnp.asarray(rmap),
                                         jnp.asarray(wmap))
                else:
                    nxt, self.cache = fn(self._versions[ver],
                                         jnp.asarray(self._tok[:, None]),
                                         jnp.asarray(self._pos),
                                         jnp.asarray(group), self.cache,
                                         jnp.asarray(rids),
                                         jnp.asarray(gidx))
                with obs.span("serve.readback"):
                    nxt = np.asarray(nxt)
                dispatches += 1
                self.decode_dispatches += 1
                self.decode_rows_live += int(group.sum())
                self.decode_rows_total += self.max_batch
                for s in range(self.max_batch):
                    if not group[s]:
                        continue
                    st = self._slots[s]
                    st.gen.append(int(nxt[s]))
                    self._pos[s] += 1
                    self._tok[s] = int(nxt[s])
                    if len(st.gen) >= st.req.max_new:
                        completed.append(self._finish(s))
                if obs.tracing():
                    sp.set_metadata(**args)
        return dispatches

    def _decode_args(self, pos: np.ndarray) -> Dict[str, int]:
        """The ``mlitb.serve.decode`` span's counters for a dispatch over
        rows at positions ``pos``: live KV tokens (``pos + 1`` a row)
        and, paged, the live pages among the pages walked."""
        args = {"rows_live": len(pos), "kv_tokens_live": int(pos.sum())
                + len(pos)}
        if self.paged:
            args["kv_pages_live"] = int((pos // self.page_size).sum()) \
                + len(pos)
            args["kv_pages_walked"] = self.pages_walked
        if self.hybrid:
            # the Mamba layers read and write every slot's state
            args["state_rows_live"] = len(pos)
            args["state_rows_walked"] = self.max_batch
        return args

    def _step_speculative(self, completed: List[Completion],
                          verify_shapes: List[Tuple[int, int]]
                          ) -> Tuple[int, int]:
        """Speculative decode round (docs/serving.md §9): draft up to k
        tokens per live row in ONE dispatch, then verify each version
        group with ONE prefill-chunk-shaped dispatch over [current
        token, drafts...] at the row's frontier. Greedy accept rule: the
        longest prefix of drafts matching the target's own argmax chain,
        plus the target token at the first mismatch (or the bonus token
        when everything matched) — by induction every emitted token is
        exactly the target model's greedy choice, so the stream is
        BIT-EQUAL to non-speculative decoding; the draft only decides
        how many of those tokens one dispatch advances. Rejected drafts
        leave stale KV past the new frontier, which the next chunk
        overwrites before any query can attend it (write-then-attend,
        contiguous from the frontier — same argument as the slot-reuse
        invariant)."""
        spec = self._spec
        B, W, k = self.max_batch, spec.window, spec.k
        win = np.zeros((B, W), np.int32)
        hlen = np.zeros(B, np.int32)
        kb = np.zeros(B, np.int32)
        for s in range(B):
            if not self._live[s]:
                continue
            st = self._slots[s]
            hist = list(st.req.prompt) + st.gen
            take = min(len(hist), W - k)
            win[s, :take] = hist[-take:]
            hlen[s] = take
            # never draft past the request's budget: emitting <= kb+1
            # tokens keeps gen from overshooting max_new, and keeps
            # every KV write within the allocated pages / max_seq
            kb[s] = min(k, st.req.max_new - len(st.gen) - 1)
        draft_dispatches = 0
        if (kb > 0).any():
            dfn = self._get_draft_fn()
            drafts = dfn(spec.draft_params, jnp.asarray(win),
                         jnp.asarray(hlen))
            with obs.span("serve.readback"):
                drafts = np.asarray(drafts)
            draft_dispatches = 1
            self.drafted += int(kb.sum())
        else:
            drafts = np.zeros((B, k), np.int32)
        vcap = pow2_bucket(k + 1)       # pinned: ONE verify bucket ever
        dispatches = 0
        vers = sorted({self._slots[s].ver
                       for s in range(B) if self._live[s]})
        for ver in vers:
            with obs.span("serve.decode") as sp:
                group = np.array([self._live[s]
                                  and self._slots[s].ver == ver
                                  for s in range(B)], bool)
                pos = self._pos[group]      # a copy: pos before this
                tokens = np.zeros((B, vcap), np.int32)
                off = np.zeros(B, np.int32)
                cl = np.zeros(B, np.int32)
                for s in range(B):
                    if not group[s]:
                        continue
                    nkb = int(kb[s])
                    tokens[s, 0] = self._tok[s]
                    tokens[s, 1:1 + nkb] = drafts[s, :nkb]
                    off[s] = self._pos[s]
                    cl[s] = 1 + nkb
                fn = self._get_verify_fn(vcap)
                if self.paged:
                    rmap, wmap = self._decode_page_maps(group)
                    nxt, self.cache = fn(self._versions[ver],
                                         jnp.asarray(tokens),
                                         jnp.asarray(off), jnp.asarray(cl),
                                         jnp.asarray(rmap),
                                         jnp.asarray(wmap), self.cache)
                else:
                    nxt, self.cache = fn(self._versions[ver],
                                         jnp.asarray(tokens),
                                         jnp.asarray(off), jnp.asarray(cl),
                                         self.cache)
                with obs.span("serve.readback"):
                    nxt = np.asarray(nxt)   # (B, vcap) greedy per chunk col
                dispatches += 1
                self.decode_dispatches += 1
                self.decode_rows_live += int(group.sum())
                self.decode_rows_total += B
                verify_shapes.append((B, vcap))
                for s in range(B):
                    if not group[s]:
                        continue
                    st = self._slots[s]
                    nkb = int(kb[s])
                    acc = 0
                    while (acc < nkb
                           and int(drafts[s, acc]) == int(nxt[s, acc])):
                        acc += 1
                    emitted = [int(t) for t in drafts[s, :acc]] \
                        + [int(nxt[s, acc])]
                    st.gen.extend(emitted)
                    self._pos[s] += len(emitted)
                    self._tok[s] = emitted[-1]
                    self.spec_rounds += 1
                    self.accepted += acc
                    if len(st.gen) >= st.req.max_new:
                        completed.append(self._finish(s))
                if obs.tracing():
                    sp.set_metadata(**self._decode_args(pos))
        return dispatches, draft_dispatches

    # ------------------------------------------------------------------
    @property
    def has_work(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    def _begin_run(self):
        assert not self.has_work, \
            "engine already has work in flight; one run_* call at a time"
        # throughput counters are PER RUN (trace_count and the step-fn
        # cache are engine-lifetime: reuse across runs shares traces)
        self.engine_steps = 0
        self.prefill_tokens = 0
        self.prefill_chunks = 0
        self.decode_dispatches = 0
        self.decode_rows_live = 0
        self.decode_rows_total = 0
        self.swap_count = 0
        self.shed_log = []
        self.queue_peak = 0
        self.concurrency_peak = 0
        self.pages_peak = 0
        self.prefix_hits = 0
        self.reused_tokens = 0
        self.decode_kv_tokens = 0
        self.decode_pages_live = 0
        self.decode_pages_walked = 0
        self.spec_rounds = 0
        self.drafted = 0
        self.accepted = 0
        self._rids_active = set()   # rids are scoped per run: a replay
                                    # reuses the same ids legitimately

    def _stats(self, completions: List[Completion],
               makespan: float) -> ServeStats:
        """Run totals. Time to first token and the queue wait are read on
        the same footing as ``latency``: the latency less the time from
        the first token's (admission's) step to the finish; they and the
        mean gap between tokens cover the completions whose clocks were
        stamped (steps given ``now``)."""
        lats = [c.latency for c in completions]
        gen = sum(int(c.tokens.size) for c in completions)
        clocked = [c for c in completions if c.first_token is not None]
        ttft = [c.latency - (c.finish - c.first_token) for c in clocked]
        wait = [c.latency - (c.finish - c.admitted) for c in clocked]
        gaps = sum(int(c.tokens.size) - 1 for c in clocked)
        versions: Dict[int, int] = {}
        for c in completions:
            versions[c.version] = versions.get(c.version, 0) + 1
        return ServeStats(
            n_requests=len(completions), gen_tokens=gen,
            makespan=makespan,
            tokens_per_s=gen / makespan if makespan > 0 else float("inf"),
            p50_latency=float(np.percentile(lats, 50)) if lats else 0.0,
            p95_latency=float(np.percentile(lats, 95)) if lats else 0.0,
            engine_steps=self.engine_steps,
            prefill_tokens=self.prefill_tokens,
            decode_rows_live=self.decode_rows_live,
            decode_rows_total=self.decode_rows_total,
            trace_count=self._trace_count, completions=completions,
            prefill_chunks=self.prefill_chunks,
            decode_dispatches=self.decode_dispatches,
            swap_count=self.swap_count, versions_served=versions,
            n_shed=len(self.shed_log), queue_peak=self.queue_peak,
            shed=list(self.shed_log),
            concurrency_peak=self.concurrency_peak,
            pages_peak=self.pages_peak, prefix_hits=self.prefix_hits,
            reused_tokens=self.reused_tokens,
            decode_kv_tokens=self.decode_kv_tokens,
            decode_pages_live=self.decode_pages_live,
            decode_pages_walked=self.decode_pages_walked,
            ttft_p50=float(np.percentile(ttft, 50)) if ttft else None,
            ttft_p95=float(np.percentile(ttft, 95)) if ttft else None,
            queue_wait_p95=float(np.percentile(wait, 95)) if wait else None,
            token_gap_mean=sum(c.finish - c.first_token for c in clocked)
            / gaps if gaps else None,
            spec_rounds=self.spec_rounds, drafted=self.drafted,
            accepted=self.accepted)

    def run_simulated(self, requests: Sequence[ServeRequest],
                      cost: "Any",
                      swaps: Sequence[Tuple[float, PyTree, int]] = ()
                      ) -> ServeStats:
        """Open-loop run on a discrete-event clock: requests arrive at
        ``req.arrival``, each engine step advances the clock by the cost
        model's charge for the PADDED shapes it executed, and optional
        ``swaps`` — ``(t, params, version)`` triples — hot-swap the model
        when the clock reaches ``t``. Outputs are the real model's
        tokens; only time is simulated."""
        session = SimulatedServeSession(self, cost, requests)
        for t, params, version in swaps:
            session.push_swap(t, params, version)
        session.drain()
        return session.stats()

    def run_closed_loop(self,  # reprolint: exempt[RL002]
                        requests: Sequence[ServeRequest]) -> ServeStats:
        """All requests available at t=0; real wall-clock timing (the one
        deliberately non-simulated entry point, hence the RL002 exempt)."""
        self._begin_run()
        for r in sorted(requests, key=lambda r: r.rid):
            self.submit(r, now=0.0)  # closed loop: everything is offered
                                     # at t=0, so sheds stamp t=0 too
        t0 = time.perf_counter()    # drain whatever was admitted
        out: List[Completion] = []
        while self.has_work:
            rep = self.step()
            now = time.perf_counter() - t0
            for c in rep.completed:
                c.finish = now
                c.latency = now
                out.append(c)
        return self._stats(out, makespan=time.perf_counter() - t0)


class SimulatedServeSession:
    """Incremental discrete-event driver over one engine: feed it
    timestamped arrivals and param swaps, then ``advance_to(t)`` — this
    is how launch/train_serve.py threads ONE clock through the training
    event loop and the serving engine (training iterations advance the
    shared clock; the session catches the engine up to it, applying the
    published params at their publish times)."""

    def __init__(self, engine: ServingEngine, cost: Any,
                 requests: Sequence[ServeRequest] = ()):
        engine._begin_run()
        self.engine = engine
        self.cost = cost
        self._reqs = sorted(requests, key=lambda r: (r.arrival, r.rid))
        self._by_rid = {r.rid: r for r in self._reqs}
        assert len(self._by_rid) == len(self._reqs), "duplicate request ids"
        self._i = 0
        self._swaps: Deque[Tuple[float, PyTree, Optional[int]]] = deque()
        self.clock = 0.0
        self.completions: List[Completion] = []

    # ------------------------------------------------------------------
    @property
    def done(self) -> bool:
        """Every request is ANSWERED: completed, or explicitly shed
        (``engine.shed_log`` is reset at session start, so its length is
        exactly this session's shed count)."""
        return len(self.completions) + len(self.engine.shed_log) \
            == len(self._reqs)

    def push_swap(self, t: float, params: PyTree,
                  version: Optional[int] = None) -> None:
        """Schedule a hot-swap at clock time ``t`` (pushes must arrive in
        time order — the natural order of a training loop's publishes)."""
        if self._swaps and t < self._swaps[-1][0]:
            raise ValueError("swaps must be pushed in time order")
        self._swaps.append((float(t), params, version))

    # ------------------------------------------------------------------
    def _apply_due(self) -> None:
        while self._swaps and self._swaps[0][0] <= self.clock + 1e-12:
            _, params, version = self._swaps.popleft()
            self.engine.swap_params(params, version)
            swap_time = getattr(self.cost, "swap_time", None)
            if swap_time is not None:
                self.clock += swap_time()
        while self._i < len(self._reqs) \
                and self._reqs[self._i].arrival <= self.clock + 1e-12:
            # a False return means the request shed at admission — the
            # refusal is already in engine.shed_log, nothing to track
            self.engine.submit(self._reqs[self._i], now=self.clock)
            self._i += 1

    def _next_event(self) -> Optional[float]:
        times = []
        if self._i < len(self._reqs):
            times.append(self._reqs[self._i].arrival)
        if self._swaps:
            times.append(self._swaps[0][0])
        return min(times) if times else None

    def _step_once(self) -> None:
        rep = self.engine.step(now=self.clock)
        dt = 0.0
        for shape in rep.prefill_shapes:
            dt += self.cost.prefill_time(*shape)
        draft_time = getattr(self.cost, "draft_time", None)
        if rep.draft_dispatches and draft_time is not None:
            spec = self.engine._spec
            dt += rep.draft_dispatches * draft_time(
                spec.k, self.engine.max_batch, spec.window)
        paged_time = getattr(self.cost, "decode_time_paged", None)
        flash_time = getattr(self.cost, "decode_time_flash", None)
        if rep.verify_shapes:
            # speculative: verification is a prefill-chunk dispatch, so
            # it is charged at prefill rates — that the chunk advances up
            # to k+1 tokens per row is exactly the speculative win
            for shape in rep.verify_shapes:
                dt += self.cost.prefill_time(*shape)
        elif rep.decode_pages and paged_time is not None:
            # paged engine: decode streams only the LIVE pages, which is
            # the whole memory-bound win (core/simulation.ServeCostModel)
            for pages in rep.decode_pages:
                dt += paged_time(pages, self.engine.pages_per_slot)
        elif rep.decode_kv and flash_time is not None:
            # dense flash kernel: the pos-bounded scan touches only the
            # live KV tokens, not the full max_seq rectangle
            for kv in rep.decode_kv:
                dt += flash_time(kv, self.engine.max_seq)
        else:
            dt += rep.decode_dispatches \
                * self.cost.decode_time(self.engine.max_batch)
        self.clock += dt
        for c in rep.completed:
            req = self._by_rid[c.rid]
            c.finish = self.clock
            c.latency = self.clock - req.arrival + 2.0 * req.client_latency
            self.completions.append(c)

    def advance_to(self, t_end: float) -> None:
        """Run the engine until the clock reaches ``t_end`` (idle gaps
        jump the clock; work in progress may overshoot — time is charged
        when a step completes, never sliced)."""
        while self.clock < t_end:
            self._apply_due()
            if self.engine.has_work:
                self._step_once()
            else:
                nxt = self._next_event()
                if nxt is None or nxt > t_end:
                    self.clock = t_end
                else:
                    self.clock = max(self.clock, nxt)
        self._apply_due()

    def drain(self) -> None:
        """Run until every submitted-or-future request has completed."""
        while not self.done:
            self._apply_due()
            if self.engine.has_work:
                self._step_once()
            else:
                nxt = self._next_event()
                assert nxt is not None, "no work left but requests missing"
                self.clock = max(self.clock, nxt)

    def stats(self) -> ServeStats:
        # makespan is the LAST COMPLETION's clock, not the session clock:
        # advance_to() may have idled the clock past the serving work
        # (e.g. a training horizon longer than the request schedule), and
        # throughput must not be diluted by that idle tail
        makespan = max((c.finish for c in self.completions),
                       default=self.clock)
        return self.engine._stats(self.completions, makespan=makespan)
