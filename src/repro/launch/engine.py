"""Continuous-batching serve engine over a slot-allocated quantized KV cache.

``ServeEngine`` is a thin façade over two modules:

- ``repro.launch.scheduler`` — host-side policy: request queue, slot
  lifecycle, and (in unified mode) the token-budget planner;
- ``repro.launch.executor`` — device side: jitted/shard_mapped prefill,
  decode, and the unified ragged step, with donated caches.

Two scheduling modes share the same API, caches, and metrics:

**legacy** (default, the historical engine — and the oracle the unified
mode is golden-tested against). Each step interleaves:

  1. **admit** — while a slot is free and the queue is non-empty, pop the
     oldest request, prefill it alone (batch-1) against its slot's cache
     rows, and emit its first token from the prefill logits (TTFT).
  2. **decode** — one batched greedy decode step over *all* occupied
     slots at once; every slot sits at its own sequence position, so the
     cache write and RoPE/attention run with per-slot position vectors
     (``models.layers.cache_update*`` with (B,) ``pos``).
  3. **retire** — slots whose request hit ``max_new_tokens`` (or the
     optional ``eos_id``) return their result and go back on the free
     list; the next queued request is admitted on the following step.

Slot reuse needs no cache zeroing: a new occupant's prefill overwrites
rows [0, P) and every stale row beyond the slot's position is masked by
the causal (position >= kv position) test inside ``chunked_attention``.
Decode always runs the full ``n_slots`` batch (free slots carry a dummy
token at position 0 whose output is discarded) so the decode step
compiles exactly once; prefill compile count is tamed by pow-2
**bucketing** (default) or fixed-size **chunked prefill**
(``prefill_chunk=C``, paged mode).

Legacy's weakness is head-of-line coupling: prefill-on-admit runs as its
own dispatch(es) *before* the decode step, so a long admission stalls
every in-flight decode (TTFT work blocks ITL).

**unified** (``schedule="unified"``) removes that coupling with a
vLLM-style token budget: each step the scheduler packs up to
``max_batch_tokens`` of work — one decode token per running slot plus
prefill *chunks* for admitting ones — into ONE ragged model invocation
(``models.dense.ragged_step``) against the paged KV pool. Long prompts
spread across steps instead of stalling them, decode tokens ride in
every step, and the fixed packing width gives O(1) step compile shapes.
Decoded tokens are **bitwise identical** to legacy (the per-row numerics
are unchanged; the golden fixtures run against both modes).

``paged=True`` (implied by unified) swaps the slot-contiguous cache for
a **paged KV pool** (``repro.launch.paged``): fixed-size pages allocated
lazily as sequences grow, per-slot page tables gathered on device,
token-identical output to the slot cache. See
``src/repro/launch/README.md`` for diagrams and the pool sizing formula.
"""
from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import live_pages, ragged_work
# Re-exported for backward compatibility: these historically lived here.
from repro.launch.executor import (LegacyExecutor, RaggedExecutor,
                                   jitted_model_fns)  # noqa: F401
from repro.launch.scheduler import (Request, RequestResult, SeqState,
                                    TokenBudgetScheduler)
from repro.launch.tracing import span


@dataclasses.dataclass
class _Active:
    """Legacy-mode per-slot record (unified mode uses ``SeqState``)."""
    req: Request
    slot: int
    generated: list
    admit_step: int
    ttft_s: float


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unobserved step: its plan, the device token
    array its observation will fetch, whether the token vector is
    slot-major (fused decode fast path) or consumer-row major (ragged
    step), and the engine cycle that dispatched it. A step whose
    attention runs a paged kernel also carries (work done, work of a
    walk over whole tables) by that kernel's rule (``work``): pages
    fetched for a fast-path step, query rows x pages computed for a
    unified one."""
    plan: object
    toks: object
    slot_major: bool
    step: int
    work: Optional[tuple] = None

    @property
    def kind(self) -> str:
        """``"decode"`` (the pure-decode fast path) or ``"unified"``."""
        return "decode" if self.slot_major else "unified"


def serving_params(make_serving_params, params):
    """``make_serving_params(params)`` as one jitted program. Run eagerly,
    its per-layer steps unpacked every concatenated weight of all layers
    to int32 at once; at Mistral-NeMo-12B's widths that ran the chip out
    of memory. On an accelerator the program takes ``params`` over (their
    buffers are donated); on the CPU, where they live in host memory, the
    caller's tree stays valid. A tree that is served already (the program
    would change none of its structure) is returned as it is."""
    served = jax.eval_shape(make_serving_params, params)
    if jax.tree.structure(served) == jax.tree.structure(params):
        return params
    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(make_serving_params, donate_argnums=donate)(params)


# ------------------------------------------------------------------ engine

class ServeEngine:
    """Continuous-batching greedy-decode engine (see module docstring).

    ``model``/``params`` follow ``repro.models.Model``; the model family
    must support per-slot position vectors in its decode cache (dense
    does). ``max_len`` bounds prompt + generated tokens per slot.

    On an accelerator the engine takes ``params`` over when it serves
    them (``fused``, see ``serving_params``): their buffers are donated.
    A caller that builds another engine from the same weights passes
    ``engine.params``, which are served already and taken as they are.
    """

    _SLOT_FAMILIES = ("dense", "moe", "vlm")   # families with (B,) pos decode

    def __init__(self, model, params, *, n_slots: int = 4,
                 max_len: int = 256, eos_id: Optional[int] = None,
                 mesh=None, tp_axis: str = "model",
                 tp_mode: str = "gather", tp_kernels: bool = False,
                 paged: bool = False, page_size: int = 16,
                 prefill_chunk: int = 0, n_pages: int = 0,
                 bucket: bool = True, paged_kernel: bool = False,
                 schedule: str = "legacy", max_batch_tokens: int = 0,
                 fused: bool = True, prefix_cache: bool = False,
                 speculative_k: int = 0, draft=None,
                 adaptive_spec: bool = False,
                 pipeline: Optional[bool] = None):
        family = getattr(model.cfg, "family", "dense")
        if family not in self._SLOT_FAMILIES:
            raise NotImplementedError(
                f"ServeEngine needs per-slot position vectors in decode, "
                f"implemented for {self._SLOT_FAMILIES}; got family "
                f"{family!r}")
        if schedule not in ("legacy", "unified"):
            raise ValueError(f"schedule must be 'legacy' or 'unified', "
                             f"got {schedule!r}")
        if speculative_k < 0:
            raise ValueError(
                f"speculative_k must be >= 0, got {speculative_k}")
        if speculative_k and schedule != "unified":
            raise ValueError(
                "speculative_k needs schedule='unified' (the draft/verify "
                "cycle runs inside the token-budgeted ragged step)")
        if speculative_k and draft is None:
            raise ValueError(
                "speculative_k needs draft=(draft_model, draft_params) — "
                "e.g. the int4-packed quantization of the target "
                "checkpoint (launch.serve.build_draft_model)")
        if adaptive_spec and not speculative_k:
            raise ValueError("adaptive_spec needs speculative_k > 0 "
                             "(it tunes the per-slot draft depth)")
        self.spec_k = int(speculative_k)
        # Pipelined (depth-1 asynchronous) unified loop: pack + dispatch
        # step N+1 while step N executes, observe step N's device-
        # resident tokens afterwards. Default ON for unified serving;
        # REPRO_SYNC_STEP=1 forces the synchronous loop (honest blocked
        # per-step timing spans for profiling).
        if pipeline is None:
            pipeline = (schedule == "unified"
                        and not os.environ.get("REPRO_SYNC_STEP"))
        if pipeline and schedule != "unified":
            raise ValueError("pipeline=True needs schedule='unified' "
                             "(legacy prefill-on-admit is inherently "
                             "synchronous); pass pipeline=False or None")
        self.pipeline = bool(pipeline)
        self._inflight: Optional[_InFlight] = None
        if schedule == "unified":
            paged = True    # the unified step serves from the paged pool
        elif max_batch_tokens:
            raise ValueError("max_batch_tokens needs schedule='unified' "
                             "(legacy packs per-slot, not per-token)")
        self.model = model
        self.schedule = schedule
        self.n_slots, self.max_len, self.eos_id = n_slots, max_len, eos_id
        self.paged, self.bucket = paged, bucket
        self.prefill_chunk, self.paged_kernel = prefill_chunk, paged_kernel
        if paged:
            from repro.launch.paged import PagePool, SlotPageTables
            from repro.models.layers import KV_QUANT_GROUP
            if getattr(model, "init_paged_cache", None) is None:
                raise NotImplementedError(
                    f"family {family!r} has no paged KV cache")
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            if prefill_chunk < 0:
                raise ValueError(
                    f"prefill_chunk must be >= 0, got {prefill_chunk}")
            if model.cfg.kv_quant_bits and page_size % KV_QUANT_GROUP:
                raise ValueError(
                    f"page_size={page_size} must be a multiple of the KV "
                    f"quant scale group ({KV_QUANT_GROUP})")
            if prefill_chunk and prefill_chunk % page_size \
                    and schedule == "legacy":
                # unified chunks are budget-sliced scatter writes, free of
                # page alignment; legacy chunks must write whole pages
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be a multiple of "
                    f"page_size={page_size} (chunks write whole pages)")
            # logical rows per slot, rounded up to whole pages; a
            # speculative verify writes up to spec_k rows past the last
            # decode position, so the table covers max_len + spec_k
            self._kv_len = (-(-(max_len + self.spec_k) // page_size)
                            * page_size)
            n_ptab = self._kv_len // page_size
            n_pages = n_pages or 1 + n_slots * n_ptab  # worst case + null
            self.pool = PagePool(n_pages, page_size)
            self.tables = SlotPageTables(self.pool, n_slots, n_ptab)
            self.prefix = None
            if prefix_cache:
                # automatic prefix caching: COW page sharing across
                # requests (launch.paged.PrefixCache). The config digest
                # keys the trie so pages can never cross quantization
                # configs.
                from repro.launch.paged import PrefixCache
                cfg = model.cfg
                self.prefix = PrefixCache(
                    self.pool, page_size,
                    config_key=(family, cfg.n_layers, cfg.n_kv_heads,
                                cfg.head_dim, cfg.kv_quant_bits,
                                str(getattr(cfg, "dtype", "?"))))
            cache = model.init_paged_cache(n_pages, page_size)
            cache = dict(cache)
            # Speculative decoding: the draft model's KV lives in a
            # PARALLEL quantized pool with identical geometry, behind its
            # own tables (no prefix sharing — draft pages are always
            # private), admitted/grown/shrunk/released in lockstep with
            # the target tables by the scheduler.
            self.draft_pool = self.draft_tables = None
            draft_exec = None
            if self.spec_k:
                draft_model, draft_params = draft
                if getattr(draft_model, "init_paged_cache", None) is None \
                        or draft_model.ragged_step is None:
                    raise NotImplementedError(
                        "the draft model needs paged-cache + ragged-step "
                        "support (family "
                        f"{getattr(draft_model.cfg, 'family', '?')!r})")
                if draft_model.cfg.vocab != model.cfg.vocab:
                    raise ValueError(
                        f"draft vocab {draft_model.cfg.vocab} != target "
                        f"vocab {model.cfg.vocab} — drafted token ids "
                        f"must be target token ids")
                self.draft_pool = PagePool(n_pages, page_size)
                self.draft_tables = SlotPageTables(self.draft_pool,
                                                   n_slots, n_ptab)
                dmsp = getattr(draft_model, "make_serving_params", None)
                if fused and dmsp is not None:
                    # the draft always runs single-device plain jit (even
                    # under a mesh), so it can always take the fused path
                    draft_params = dmsp(draft_params)
                draft_cache = dict(
                    draft_model.init_paged_cache(n_pages, page_size))
                draft_exec = (draft_model, draft_params, draft_cache)
        else:
            if prefix_cache:
                raise ValueError("prefix_cache needs paged=True (cached "
                                 "prefixes are shared pool pages)")
            self.prefix = None
            self.draft_pool = self.draft_tables = None
            draft_exec = None
            if prefill_chunk:
                raise ValueError("prefill_chunk needs paged=True (the slot "
                                 "cache keeps whole-prompt prefill; use "
                                 "bucket=True to bound its compile count)")
            if paged_kernel:
                raise ValueError("paged_kernel needs paged=True")
            self._kv_len = max_len
            cache = model.init_cache(n_slots, max_len)
            cache = {k: v for k, v in cache.items() if k != "pos"}
        self.quantized_kv = "k_scale" in cache
        self._page_bytes = (sum(v.nbytes for v in cache.values())
                            // n_pages if paged else 0)
        self.mesh = mesh
        # Fused serving params (QKV / gate-up concat + integer-epilogue
        # colsums, models.Model.make_serving_params): single-device hot
        # path only — the concatenated output dim would split unevenly
        # across tensor-parallel head shards. Token-identical to the
        # unfused params (golden-tested), so on by default.
        msp = getattr(model, "make_serving_params", None)
        self.fused = bool(fused and msp is not None and mesh is None)
        if self.fused:
            params = serving_params(msp, params)
        tp_kw = dict(mesh=mesh, tp_axis=tp_axis, tp_mode=tp_mode,
                     tp_kernels=tp_kernels)
        if schedule == "unified":
            # speculative mode packs k+1 verify rows per decoding slot,
            # so the default budget scales with the spec width and an
            # explicit budget must still fit every slot's verify item
            self.max_batch_tokens = max_batch_tokens or max(
                16, 2 * n_slots, n_slots * (self.spec_k + 2))
            if self.max_batch_tokens < n_slots * (self.spec_k + 1):
                raise ValueError(
                    f"max_batch_tokens={self.max_batch_tokens} must be >= "
                    f"n_slots*(speculative_k+1)="
                    f"{n_slots * (self.spec_k + 1)} (every decoding slot "
                    f"packs speculative_k+1 verify rows per step)")
            self.sched = TokenBudgetScheduler(
                n_slots, self.max_batch_tokens, pool=self.pool,
                tables=self.tables, prefill_chunk=prefill_chunk,
                eos_id=eos_id, prefix=self.prefix, spec_k=self.spec_k,
                draft_tables=self.draft_tables,
                adaptive_spec=adaptive_spec)
            # XLA:CPU executes donated computations synchronously in the
            # dispatching thread, which would re-serialize the pipelined
            # loop — a pipelined engine on CPU trades the in-place cache
            # donation for asynchronous dispatch (one pool-sized output
            # buffer per step; REPRO_PIPELINE_DONATE=1 forces donation
            # back for memory profiling). Donation-capable accelerator
            # backends dispatch donated computations asynchronously, so
            # they keep the in-place update.
            donate = not (self.pipeline
                          and jax.default_backend() == "cpu"
                          and not os.environ.get("REPRO_PIPELINE_DONATE"))
            self.exec = RaggedExecutor(model, params, cache,
                                       n_slots=n_slots,
                                       paged_kernel=paged_kernel,
                                       draft=draft_exec,
                                       spec_k=self.spec_k,
                                       donate=donate, **tp_kw)
            # shared host state lives in the scheduler; alias it so the
            # introspection surface matches legacy mode
            self._queue = self.sched.queue
            self._free = self.sched.free
            self._active = self.sched.active
        else:
            self.max_batch_tokens = 0
            self.sched = None
            self.exec = LegacyExecutor(model, params, cache,
                                       n_slots=n_slots, paged=paged,
                                       paged_kernel=paged_kernel, **tp_kw)
            self._queue = deque()
            self._free = list(range(n_slots))
            self._active = {}          # slot -> _Active
        self.params = self.exec.params
        self._pos = np.zeros((n_slots,), np.int32)     # per-slot positions
        self.step_count = 0
        self._next_rid = 0
        self._dev_acc = 0.0             # device seconds within current step
        self.events: list[tuple] = []   # ("admit"|"retire", rid, slot, step)
        self.results: dict[int, RequestResult] = {}
        self.metrics = self._fresh_metrics()

    @staticmethod
    def _fresh_metrics() -> dict:
        return {"queue_depth": [], "occupancy": [],
                "resident_kv_bytes": [], "step_s": [], "device_s": [],
                "generated_tokens": 0, "decode_steps": 0}

    def reset(self) -> None:
        """Return an idle (drained) engine to its just-built state — fresh
        metrics, results, events, positions, and scheduler/executor
        counters — WITHOUT touching params, caches, or the jitted
        executables. This is the warmup/steady-state benchmark hook: run
        a workload once (pays every compile), ``reset()``, run it again
        and read pure steady-state timings. Stale KV content from the
        first run is harmless for exactly the reason slot reuse is: a
        new occupant's prefill overwrites its rows and everything past
        its position is causally masked."""
        if not self.idle:
            raise RuntimeError("reset() needs an idle engine "
                               "(drain the queue first)")
        self._pos[:] = 0
        self.step_count = 0
        self._next_rid = 0
        self._dev_acc = 0.0
        # pipelined state: idle implies nothing is in flight, but drop
        # it defensively (and forget the executor's previous-step token
        # vector + the descriptor-ring parity) so a stale step can never
        # leak into the next run — warmup reuse must start cold.
        self._inflight = None
        if getattr(self.exec, "reset_pipeline", None) is not None:
            self.exec.reset_pipeline()
        self.exec.d2h_s = 0.0
        self.events = []
        self.results = {}
        self.metrics = self._fresh_metrics()
        self.exec.n_dispatch = 0
        if self.schedule == "unified":
            self.sched.reset()
            self._free = self.sched.free    # sched.reset() rebinds its list
        else:
            self._free = list(range(self.n_slots))
        if self.paged:
            self.pool.peak_in_use = self.pool.in_use
        if self.draft_pool is not None:
            self.draft_pool.peak_in_use = self.draft_pool.in_use
        if self.prefix is not None:
            # a warm cache is server state (like compiled code): keep the
            # trie across warmup/steady resets, zero only the counters
            self.prefix.reset_stats()

    # The executor owns the device cache; expose it under the historical
    # name so engine code (and tests) read/write one source of truth.
    @property
    def _cache(self):
        return self.exec.cache

    @_cache.setter
    def _cache(self, value):
        self.exec.cache = value

    # ------------------------------------------------------------- intake

    def submit(self, prompt, max_new_tokens: int, rid: Optional[int] = None
               ) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not len(prompt):
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new_tokens({max_new_tokens}) "
                f"exceeds max_len={self.max_len}")
        if self.paged:
            # +spec_k: speculative verify rows past the decode budget
            need = self.tables.pages_for(len(prompt) + max_new_tokens
                                         + self.spec_k)
            if need > self.pool.n_pages - 1:
                raise ValueError(
                    f"request needs {need} pages but the pool only has "
                    f"{self.pool.n_pages - 1} allocatable (raise n_pages "
                    f"or max_len/page_size)")
        if rid is None:
            rid = self._next_rid
        elif (rid in self.results
              or any(r.req.rid == rid for r in self._active.values())
              or any(r.rid == rid for r in self._queue)):
            raise ValueError(f"duplicate request id {rid}")
        self._next_rid = max(self._next_rid, rid) + 1
        # monotonic clock: submit_time is only ever *differenced* against
        # later perf_counter() reads (TTFT) — wall clock (time.time) can
        # step under NTP and yield negative latencies
        self._queue.append(Request(rid, prompt, max_new_tokens,
                                   submit_time=time.perf_counter()))
        return rid

    # ---------------------------------------------- legacy slot lifecycle

    def _bucketed(self, prompt: np.ndarray):
        """Right-pad a prompt to its power-of-two bucket (compile-count
        discipline: O(log max_len) prefill shapes instead of one per
        distinct length). Returns (padded tokens, logits row index).
        Padded rows write garbage k/v past the prompt — causally masked,
        then overwritten by decode before they are ever attendable."""
        p = len(prompt)
        if not self.bucket:
            return prompt, p - 1
        width = max(8, 1 << (p - 1).bit_length())
        width = min(width, self._kv_len if self.paged else self.max_len)
        if width <= p:
            return prompt, p - 1
        return np.pad(prompt, (0, width - p)), p - 1

    def _prefill_paged(self, req: Request, slot: int, start: int = 0):
        """Prefill rows [start, P) into the slot's pages: one bucketed
        call, or fixed-size chunks at successive offsets (ONE compile
        total) when ``prefill_chunk`` is set. ``start > 0`` is the prefix
        cache's first-miss offset — rows [0, start) are already served by
        shared (or COW-copied) pages, so their prefill is skipped
        entirely; a chunked span starting mid-page writes only the rows
        past the COW boundary (numerically a suffix of the chunked
        schedule the golden fixtures already pin)."""
        p = len(req.prompt)
        row = jnp.asarray(self.tables.table[slot:slot + 1])
        chunk = self.prefill_chunk
        if not chunk:
            toks, last = self._bucketed(req.prompt[start:])
            spans = [(toks, start, last)]
        else:
            spans = []
            for off in range(start, p, chunk):
                toks = np.zeros((chunk,), np.int32)
                n = min(chunk, p - off)
                toks[:n] = req.prompt[off:off + n]
                spans.append((toks, off, int(np.clip(p - 1 - off, 0,
                                                     chunk - 1))))
        logits = None
        for toks, off, last in spans:
            logits, row = self.exec.prefill_paged_span(toks, row, off, last)
        return logits

    def _admit(self) -> None:
        while self._free and self._queue:
            head = self._queue[0]
            hit, pages = 0, []
            if self.paged:
                budget_tokens = len(head.prompt) + head.max_new_tokens
                if self.prefix is not None:
                    hit, pages = self.prefix.lookup(head.prompt)
                    ok = self.prefix.make_room(self.tables, budget_tokens,
                                               hit_tokens=hit,
                                               protect=pages)
                else:
                    ok = self.tables.can_admit(budget_tokens)
                if not ok:
                    break                   # head-of-line wait (stays FIFO)
            slot = min(self._free)          # deterministic: lowest free slot
            self._free.remove(slot)
            req = self._queue.popleft()
            p = len(req.prompt)
            td = time.perf_counter()
            if self.paged:
                self.tables.admit_prefix(slot, pages, hit, p,
                                         budget_tokens=p
                                         + req.max_new_tokens)
                if self.prefix is not None:
                    self.prefix.note(hit, p)
                    cow = self.tables.ensure_writable(slot, hit)
                    if cow:
                        self.prefix.cow_copies += len(cow)
                        self.exec.copy_pages(cow)
                self.tables.assert_writable(slot, hit, p - 1)
                logits = self._prefill_paged(req, slot, start=hit)
            else:
                toks, last = self._bucketed(req.prompt)
                logits = self.exec.prefill_slot(toks, slot, last)
            logits.block_until_ready()
            self._dev_acc += time.perf_counter() - td
            if self.prefix is not None:
                # prefill landed -> adopt the full prompt pages
                self.prefix.register(req.prompt,
                                     self.tables.owned_pages(slot))
            self._pos[slot] = p
            tok = int(np.argmax(np.asarray(logits[0, -1])))
            rec = _Active(req, slot, [tok], self.step_count,
                          time.perf_counter() - req.submit_time)
            self.metrics["generated_tokens"] += 1
            self.events.append(("admit", req.rid, slot, self.step_count))
            if self._finished(rec):
                self._retire(rec)
            else:
                self._active[slot] = rec

    def _finished(self, rec: _Active) -> bool:
        # mirrors TokenBudgetScheduler._finished: guard empty generated
        # and never let eos_id=None shadow a real token id
        if len(rec.generated) >= rec.req.max_new_tokens:
            return True
        return (self.eos_id is not None and bool(rec.generated)
                and rec.generated[-1] == self.eos_id)

    def _retire(self, rec: _Active) -> None:
        rid = rec.req.rid
        if rid in self.results:
            raise RuntimeError(f"request {rid} retired twice")
        self.results[rid] = RequestResult(
            rid=rid,
            tokens=np.concatenate([rec.req.prompt,
                                   np.asarray(rec.generated, np.int32)]),
            prompt_len=len(rec.req.prompt),
            ttft_s=rec.ttft_s,
            admit_step=rec.admit_step,
            retire_step=self.step_count,
        )
        self.events.append(("retire", rid, rec.slot, self.step_count))
        self._active.pop(rec.slot, None)
        self._pos[rec.slot] = 0       # free slots idle at position 0
        if self.paged:
            self.tables.release(rec.slot)
        self._free.append(rec.slot)

    # --------------------------------------------------------------- step

    def resident_kv_bytes(self) -> int:
        """KV bytes actually reserved for live sequences: allocated pages
        (paged) or the whole slot allocation (contiguous — every slot
        reserves max_len rows up front regardless of use). Reported in
        BOTH modes so slot-vs-paged benchmark rows compare like for
        like. With a prefix cache, pages shared across slots count once
        (the dedup win) and pages retained only by the cache don't count
        as live at all — cache retention is reported separately
        (``cached_kv_bytes`` in ``summary()``)."""
        if self.paged:
            n = (self.tables.slot_mapped_pages if self.prefix is not None
                 else self.pool.in_use)
            return n * self._page_bytes
        return sum(v.nbytes for v in self._cache.values())

    def step(self) -> dict:
        """One engine cycle; returns step stats. Legacy: admit (prefill
        dispatches) + one batched decode + retire. Unified: plan one
        token-budgeted ragged step, run it, feed tokens back, retire;
        the stats are the cycle's record (``_record``)."""
        with span("engine.step", step=self.step_count):
            if self.schedule == "unified":
                if self.pipeline:
                    return self._step_pipelined()
                return self._step_sync()
            return self._step_legacy()

    def _step_legacy(self) -> dict:
        t0 = time.perf_counter()
        self._dev_acc = 0.0
        events_before = len(self.events)
        self._admit()
        admitted = len(self.events) > events_before
        self.metrics["queue_depth"].append(len(self._queue))
        occ = len(self._active) / self.n_slots
        self.metrics["occupancy"].append(occ)
        if self._active:
            toks = np.zeros((self.n_slots, 1), np.int32)
            for slot, rec in self._active.items():
                toks[slot, 0] = rec.generated[-1]
                if self.paged:   # a new page the instant pos crosses one
                    self.tables.ensure(slot, int(self._pos[slot]))
        # sampled after this step's page growth so the mean/peak include
        # the pages the decode write below is about to land in
        self.metrics["resident_kv_bytes"].append(self.resident_kv_bytes())
        if self._active:
            table = jnp.asarray(self.tables.table) if self.paged else None
            td = time.perf_counter()
            d2h0 = self.exec.d2h_s
            next_toks = self.exec.decode(toks, self._pos, table)
            # the decode span is compute-only: the executor's (tiny)
            # token D2H copy is attributed to d2h_s, not device time
            self._dev_acc += (time.perf_counter() - td
                              - (self.exec.d2h_s - d2h0))
            self.metrics["decode_steps"] += 1
            for slot, rec in list(self._active.items()):
                self._pos[slot] += 1          # the fed token was cached
                rec.generated.append(int(next_toks[slot]))
                self.metrics["generated_tokens"] += 1
                if self._finished(rec):
                    self._retire(rec)
        if admitted or occ > 0:
            self.metrics["step_s"].append(time.perf_counter() - t0)
            self.metrics["device_s"].append(self._dev_acc)
        self.step_count += 1
        return {"queue_depth": self.metrics["queue_depth"][-1],
                "occupancy": occ, "active": len(self._active)}

    def _plan_and_dispatch(self):
        """Shared front half of a unified cycle: plan, account metrics,
        run the draft cycle (speculative mode), and dispatch the packed
        step WITHOUT blocking. Returns (plan, in_flight) where in_flight
        is None for an empty plan."""
        plan = self.sched.plan(self.step_count)
        for rid, slot in plan.admitted:
            self.events.append(("admit", rid, slot, self.step_count))
        self.metrics["queue_depth"].append(len(self._queue))
        self.metrics["occupancy"].append(len(self._active) / self.n_slots)
        self.metrics["resident_kv_bytes"].append(self.resident_kv_bytes())
        if not plan.n_tokens:
            return plan, None
        if self.spec_k:
            # draft/verify cycle:
            # 1. mirror prefill chunks into the draft pool;
            # 2. ONE scan dispatch proposes k+1 tokens per slot;
            # 3. the target verifies all k+1 rows per slot in the
            #    ragged step below (greedy acceptance in observe()).
            # The draft fetch BLOCKS (acceptance packs host drafts), so
            # a pipelined speculative cycle overlaps only its pack +
            # observe host work with the in-flight target step.
            for dp in self.sched.pack_draft(plan):
                self.exec.draft_prefill(dp)
            if plan.spec:
                tok0, pos0, dtable, dsrc = self.sched.draft_inputs(plan)
                drafts = self.exec.draft_k(
                    tok0, pos0, dtable,
                    dsrc if self.pipeline else None)
                plan.spec_drafts = {
                    slot: drafts[:self.spec_k, slot]
                    for slot, _, _ in plan.spec}
        self.exec.step_idx = self.step_count
        if (plan.decode and not plan.prefill and not plan.spec
                and not plan.cow and self.exec.supports_decode_step):
            # pure-decode fast path: slot-major compact batch, one
            # dispatch through model.decode (two Pallas launches per
            # layer when the fused prologue is enabled). Token-
            # identical to the ragged pack — single-row decode
            # through the unified step already matches legacy
            # model.decode bitwise (golden-tested), and this IS the
            # legacy decode call shape.
            tok, dpos, table, src = self.sched.pack_decode(plan)
            inf = _InFlight(plan,
                            self.exec.decode_step(tok, dpos, table, src),
                            True, self.step_count,
                            (live_pages(dpos + 1, self.pool.page_size,
                                        table.shape[1]), table.size))
        else:
            packed = self.sched.pack(plan, kernel_desc=self.paged_kernel)
            if plan.cow:
                # COW page copies dispatch BEFORE the step so shared
                # content is duplicated before any divergent row lands
                self.exec.copy_pages(plan.cow)
            inf = _InFlight(plan, self.exec.step(packed), False,
                            self.step_count, self._ragged_work(packed))
        if plan.decode or plan.spec:
            self.metrics["decode_steps"] += 1
        return plan, inf

    def _observe_tokens(self, inf: _InFlight, toks: np.ndarray,
                        ahead=None) -> None:
        """Shared back half: feed a step's fetched tokens through the
        scheduler and retire what finished."""
        plan = inf.plan
        if inf.slot_major:
            # fused-decode vector is slot-indexed; consumers are decode
            # rows only (the fast path precondition)
            toks = toks[[slot for slot, _, _ in plan.decode]]
        gen_before = self.sched.gen_tokens
        retired = self.sched.observe(plan, toks, time.perf_counter(),
                                     ahead=ahead)
        # actual appended count (speculative steps emit 1..k+1 per
        # slot depending on acceptance — n_logits would overcount)
        self.metrics["generated_tokens"] += (self.sched.gen_tokens
                                             - gen_before)
        for seq in retired:
            self._retire_seq(seq)

    def _fetch(self, inf: _InFlight) -> np.ndarray:
        """Wait for a dispatched step's tokens and copy them to the
        host."""
        with span("engine.fetch", fetched=inf.step):
            return np.asarray(jax.block_until_ready(inf.toks))

    def _record(self, plan, inf: Optional[_InFlight],
                fetched: Optional[_InFlight],
                fetched_at: Optional[float]) -> dict:
        """What a unified ``step()`` returns: the cycle's counters, and
        the kind of step it dispatched (``inf``) and of the step whose
        tokens it fetched (``fetched``, at host time ``fetched_at``).
        A dispatched fast-path step adds ``decode_pages_read`` (the pages
        its decode attention fetches, by the kernel's rule) and
        ``decode_pages_table`` (rows x table width); a dispatched unified
        step that runs the ragged kernel adds ``ragged_work_read`` (query
        rows x KV pages it computes) and ``ragged_work_table`` (items x
        query rows x table width)."""
        rec = {"queue_depth": self.metrics["queue_depth"][-1],
                "occupancy": self.metrics["occupancy"][-1],
                "active": len(self._active),
                "packed_tokens": plan.n_tokens,
                "kind": inf.kind if inf else None,
                "prefill_tokens": plan.prefill_tokens,
                "fetched_kind": fetched.kind if fetched else None,
                "fetched_prefill_tokens": (fetched.plan.prefill_tokens
                                           if fetched else 0),
                "fetched_at": fetched_at}
        if inf is not None and inf.work is not None:
            name = "decode_pages" if inf.slot_major else "ragged_work"
            rec[name + "_read"], rec[name + "_table"] = inf.work
        return rec

    def _ragged_work(self, packed: dict) -> Optional[tuple]:
        """``kernels.paged_attention.ragged_work`` of a packed unified
        step whose attention runs the ragged kernel (its descriptor over
        an int8 pool), else None."""
        desc, cfg = packed.get("ragged_desc"), self.model.cfg
        if desc is None or not cfg.kv_quant_bits:
            return None
        return ragged_work(desc["lengths"], desc["qpos"],
                           cfg.n_heads // cfg.n_kv_heads, cfg.n_kv_heads,
                           cfg.head_dim, self.pool.page_size,
                           self.tables.n_ptab)

    def _step_sync(self) -> dict:
        """The synchronous unified cycle (REPRO_SYNC_STEP /
        pipeline=False): dispatch, block, observe — the per-step device
        span is an honest blocked measurement, and the step fetched is
        the one dispatched."""
        t0 = time.perf_counter()
        plan, inf = self._plan_and_dispatch()
        t1 = None
        if inf is not None:
            td = time.perf_counter()
            toks = self._fetch(inf)
            t1 = time.perf_counter()
            self._observe_tokens(inf, toks)
            self.metrics["step_s"].append(time.perf_counter() - t0)
            self.metrics["device_s"].append(t1 - td)
        self.step_count += 1
        return self._record(plan, inf, inf, t1)

    def _step_pipelined(self) -> dict:
        """The depth-1 asynchronous cycle: plan + pack + dispatch step N
        optimistically (decoding slots assumed to continue, fed tokens
        injected on device from step N-1's vector), THEN block on step
        N-1's (n_logits,) int32 tokens — the only D2H of the cycle —
        and observe them, rolling back step N's rows for any slot whose
        prediction failed (see ``TokenBudgetScheduler.observe``). All
        host work between the dispatch and the fetch is hidden under
        device compute.

        Timing spans: a (step_s, device_s) pair is appended only on
        cycles that OBSERVE a step, with device_s = the token-fetch
        wait — so span counts equal observed steps and device_s <=
        step_s still holds. REPRO_SYNC_STEP gives blocked spans
        instead."""
        t0 = time.perf_counter()
        prev = self._inflight
        self._inflight = None
        plan, inf = self._plan_and_dispatch()
        if inf is not None:
            self.sched.note_dispatch(inf.plan, slot_major=inf.slot_major)
        t1 = None
        if prev is not None:
            tw = time.perf_counter()
            toks = self._fetch(prev)
            t1 = time.perf_counter()
            self._observe_tokens(prev, toks,
                                 ahead=inf.plan if inf else None)
            self.metrics["step_s"].append(time.perf_counter() - t0)
            self.metrics["device_s"].append(t1 - tw)
        self._inflight = inf
        self.step_count += 1
        return self._record(plan, inf, prev, t1)

    def _retire_seq(self, seq: SeqState) -> None:
        """Unified-mode retirement bookkeeping (the scheduler already
        freed the slot and released its pages in ``observe``)."""
        rid = seq.req.rid
        if rid in self.results:
            raise RuntimeError(f"request {rid} retired twice")
        self.results[rid] = RequestResult(
            rid=rid,
            tokens=np.concatenate([seq.req.prompt,
                                   np.asarray(seq.generated, np.int32)]),
            prompt_len=seq.prompt_len,
            ttft_s=seq.ttft_s,
            admit_step=seq.admit_step,
            retire_step=self.step_count,
        )
        self.events.append(("retire", rid, seq.slot, self.step_count))

    @property
    def idle(self) -> bool:
        # a dispatched-but-unobserved pipelined step keeps the engine
        # non-idle even when every slot already retired (the final
        # in-flight plan can be all-stale after an eos mispredict — one
        # more drain cycle discards it)
        return (not self._queue and not self._active
                and self._inflight is None)

    def run(self, requests=None) -> dict[int, RequestResult]:
        """Submit ``requests`` (dicts with tokens/max_new_tokens, see
        ``repro.data.request_workload``) and step until drained."""
        for r in requests or ():
            self.submit(r["tokens"], r["max_new_tokens"], rid=r.get("rid"))
        t0 = time.perf_counter()
        while not self.idle:
            self.step()
        self.metrics["wall_s"] = time.perf_counter() - t0
        return self.results

    # ------------------------------------------------------------ metrics

    def summary(self) -> dict:
        m = self.metrics
        ttfts = [r.ttft_s for r in self.results.values()]
        # TTFT is a perf_counter difference end-to-end (submit ->
        # first-logit); negative means a clock regression crept back in
        assert all(t >= 0 for t in ttfts), \
            f"negative TTFT (non-monotonic clock?): {min(ttfts)}"
        step_s = m["step_s"]
        dev_s = m["device_s"]
        device_ms = 1e3 * float(np.mean(dev_s)) if dev_s else 0.0
        host_ms = (1e3 * float(np.mean(step_s)) - device_ms
                   if step_s else 0.0)
        return {
            "n_requests": len(self.results),
            "n_slots": self.n_slots,
            "steps": self.step_count,
            "decode_steps": m["decode_steps"],
            "generated_tokens": m["generated_tokens"],
            "wall_s": m.get("wall_s", 0.0),
            "tok_per_s": (m["generated_tokens"] / m["wall_s"]
                          if m.get("wall_s") else 0.0),
            "ttft_s_mean": float(np.mean(ttfts)) if ttfts else 0.0,
            "ttft_s_max": float(np.max(ttfts)) if ttfts else 0.0,
            # per-step latency percentiles: the inter-token latency a
            # decoding request observes (each step emits one token per
            # running slot; legacy admission prefills inflate the tail)
            "itl_p50_s": (float(np.percentile(step_s, 50))
                          if step_s else 0.0),
            "itl_p95_s": (float(np.percentile(step_s, 95))
                          if step_s else 0.0),
            "occupancy_mean": (float(np.mean(m["occupancy"]))
                               if m["occupancy"] else 0.0),
            "queue_depth_max": (int(np.max(m["queue_depth"]))
                                if m["queue_depth"] else 0),
            "quantized_kv": self.quantized_kv,
            "paged": self.paged,
            "schedule": self.schedule,
            "fused": self.fused,
            # hot-loop attribution: device vs host milliseconds per
            # timed step, and device dispatches per engine step
            "device_ms_mean": device_ms,
            "host_ms_mean": max(0.0, host_ms),
            "n_dispatch": self.exec.n_dispatch,
            "dispatch_per_step": (self.exec.n_dispatch
                                  / max(1, self.step_count)),
            # host-side dispatches amortized over emitted tokens — the
            # serving-level view of the two-launch decode work (device
            # kernel launches per dispatch are the roofline's column)
            "launches_per_token": (self.exec.n_dispatch
                                   / max(1, m["generated_tokens"])),
            "kv_capacity_bytes": sum(v.nbytes for v in self._cache.values()),
            "resident_kv_bytes_mean": (float(np.mean(
                m["resident_kv_bytes"])) if m["resident_kv_bytes"] else 0),
            "resident_kv_bytes_peak": (int(np.max(m["resident_kv_bytes"]))
                                       if m["resident_kv_bytes"] else 0),
            **({"page_size": self.pool.page_size,
                "n_pages": self.pool.n_pages,
                "pages_peak": self.pool.peak_in_use,
                "prefill_chunk": self.prefill_chunk,
                "prefix_cache": self.prefix is not None}
               if self.paged else {}),
            **({**self.prefix.stats(),
                "cached_kv_bytes": self.prefix.resident * self._page_bytes}
               if self.prefix is not None else {}),
            **({"max_batch_tokens": self.max_batch_tokens,
                # running counter, not a plan_log scan — the log is a
                # capped ring and may have evicted the peak step
                "packed_tokens_max": self.sched.packed_tokens_max,
                "pipeline": self.pipeline,
                "mispredicts": self.sched.mispredicts}
               if self.schedule == "unified" else
               # legacy: the per-decode-step token D2H fetch, attributed
               # separately so device_ms_mean stays compute-only
               {"d2h_ms_mean": (1e3 * self.exec.d2h_s
                                / max(1, m["decode_steps"]))}),
            **({"speculative_k": self.spec_k,
                "adaptive_spec": self.sched.adaptive_spec,
                "spec_cycles": self.sched.spec_cycles,
                "spec_drafted_tokens": self.sched.spec_drafted,
                "spec_accepted_tokens": self.sched.spec_accepted,
                "spec_acceptance_rate": (
                    self.sched.spec_accepted / self.sched.spec_drafted
                    if self.sched.spec_drafted else 0.0),
                "draft_pages_peak": self.draft_pool.peak_in_use}
               if self.spec_k else {}),
            "mesh": (dict(self.mesh.shape) if self.mesh is not None
                     else None),
        }
