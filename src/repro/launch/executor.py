"""Device side of the serve stack: the unified ragged step, its
pure-decode fast path, the speculative draft pass and the COW page copy,
each one jitted (or shard_mapped) program over the donated paged pool.

The engine façade (``repro.launch.engine``) keeps all host-side policy
(queues, slots, budgets — see ``repro.launch.scheduler``); everything
that touches a jax array lives here, in ``RaggedExecutor``: ONE
invocation per engine step runs the flat packed (T, 1) token batch —
decode rows and prefill-chunk rows together — against the paged KV pool
with per-token positions and page-table rows, returning the greedy token
only at the packed rows the scheduler marked
(``models.dense.ragged_step``). The cache is donated, so pools update in
place on donation-capable backends.

The executor owns ``params`` and ``cache`` (device_put with the
quantization-aware shardings from ``distributed.sharding`` in mesh mode)
and exposes small host-facing methods taking numpy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.tracing import span


# Device-side token injection (pipelined serving): a packed row whose fed
# token was still in flight at pack time carries ``tok_src[i] >= 0`` — the
# index of its true token inside the PREVIOUS step's device-resident
# (W,) token vector. The substitution runs inside the jitted step, so the
# host never has to wait for step N's tokens to pack and dispatch step
# N+1. ``tok_src = -1`` rows keep their host-packed token (sync mode
# passes prev_toks=None and skips the gather entirely).
def _inject_prev(tokens, prev_toks, tok_src):
    if prev_toks is None:
        return tokens
    fetched = jnp.take(prev_toks, jnp.maximum(tok_src, 0), axis=0)
    return jnp.where(tok_src[:, None] >= 0, fetched[:, None], tokens)


# The whole unified step is one jitted program: scatter-write every packed
# token's k/v, attend, and read logits (or, with ``greedy``, their argmax
# token ids — device-resident sampling) at the scheduler-marked rows. The
# cache (the global paged pools) is donated for in-place pool updates;
# ``step_fn`` (``model.ragged_step``), the kernel flag, and ``greedy``
# are static.
#
# Each donated wrapper also has an ``_async`` twin WITHOUT donation:
# XLA:CPU dispatches donated computations synchronously (the whole step
# executes inline in the dispatching thread), which would re-serialize
# the pipelined loop — a pipelined executor on the CPU backend therefore
# trades the in-place cache update for asynchronous dispatch (the pool
# round-trips through a fresh output buffer; see RaggedExecutor(donate=)).
def _unified_step_impl(step_fn, paged_kernel, greedy, params, cache,
                       tokens, pos, page_table, logit_rows, ragged_desc,
                       prev_toks, tok_src):
    tokens = _inject_prev(tokens, prev_toks, tok_src)
    cache = dict(cache, pos=pos, page_table=page_table)
    out, cache = step_fn(params, tokens, cache, logit_rows,
                         paged_kernel=paged_kernel, greedy=greedy,
                         ragged_desc=ragged_desc)
    cache.pop("pos")
    cache.pop("page_table")
    return out, cache


_unified_step = functools.partial(
    jax.jit, static_argnums=(0, 1, 2),
    donate_argnums=(4,))(_unified_step_impl)
_unified_step_async = functools.partial(
    jax.jit, static_argnums=(0, 1, 2))(_unified_step_impl)


# Pure-decode fast path: when a unified plan is decode-only (every packed
# row has q_len 1 — no prefill chunks, no speculative verify items, no COW
# copies), the ragged machinery buys nothing: the step IS a batched decode.
# Dispatching it as ``model.decode`` instead lets the layer body take the
# two-launch fused path (``models.dense._fused_decode_attn``: QKV-prologue
# kernel + paged attention, no XLA glue between them) on TPU, and is
# bitwise identical to the ragged step's decode rows everywhere (same
# per-row numerics — the property the golden fixtures pin).
# ``decode_fn`` is static; the cache (the global paged pools) is donated.
# Returns the (n_slots,) greedy token ids (device-resident sampling —
# argmax in the same program, only int32 tokens cross D2H).
def _fused_decode_step_impl(decode_fn, params, cache, tokens, pos, table,
                            prev_toks, tok_src):
    tokens = _inject_prev(tokens, prev_toks, tok_src)
    cache = dict(cache, pos=pos, page_table=table)
    logits, cache = decode_fn(params, tokens, cache)
    cache.pop("pos")
    cache.pop("page_table")
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache


_fused_decode_step = functools.partial(
    jax.jit, static_argnums=(0,),
    donate_argnums=(2,))(_fused_decode_step_impl)
_fused_decode_step_async = functools.partial(
    jax.jit, static_argnums=(0,))(_fused_decode_step_impl)


# Speculative draft pass: ONE jitted dispatch runs n_steps greedy decode
# steps of the draft model over its paged pool — lax.scan with on-device
# argmax between steps, so proposing k tokens costs one host round trip
# instead of k (the whole point on a dispatch-overhead-bound host).
# ``decode_fn`` (draft model.decode) and the step count are static; the
# draft cache is donated for in-place pool updates. Each scan iteration
# feeds the previous argmax at the next position; ``forward`` advances
# ``pos`` by 1 per step and threads ``page_table`` through the carry.
# Returns all n_steps proposed tokens (n_steps, B) — callers use the
# first k as drafts (the extra step exists so a fully-accepted block's
# bonus token leaves no draft-KV hole at pos0+k).
def _draft_scan_impl(decode_fn, n_steps, params, cache, tok0, pos0, table,
                     prev_toks, tok_src):
    tok0 = _inject_prev(tok0, prev_toks, tok_src)
    cache = dict(cache, pos=pos0, page_table=table)

    def body(carry, _):
        tok, cache = carry
        logits, cache = decode_fn(params, tok, cache)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (nxt[:, None], cache), nxt

    (_, cache), drafts = jax.lax.scan(body, (tok0, cache), None,
                                      length=n_steps)
    cache.pop("pos")
    cache.pop("page_table")
    return drafts, cache


_draft_scan = functools.partial(
    jax.jit, static_argnums=(0, 1), donate_argnums=(3,))(_draft_scan_impl)
_draft_scan_async = functools.partial(
    jax.jit, static_argnums=(0, 1))(_draft_scan_impl)


# COW page copy (prefix caching): duplicate src pages' rows into dst
# pages across every pool leaf before the step that writes the divergent
# rows. ``copy_fn`` (model.copy_paged_pages) is static; the cache is
# donated so the copy is in place on donation-capable backends. Pairs
# are padded with (0, 0) null-page self-copies (inert) to ONE fixed
# width — pow-2 ceil of n_slots, the most COW splits a single plan can
# carry — so the copy compiles exactly once per engine and never traces
# inside a timed pass. Under a mesh the pools arrive sharded (heads on
# "model", page axis whole) and jit partitions the per-page
# gather/scatter over the head shards.
def _copy_pages_impl(copy_fn, cache, src, dst):
    return copy_fn(cache, src, dst)


_copy_pages = functools.partial(
    jax.jit, static_argnums=(0,), donate_argnums=(1,))(_copy_pages_impl)
_copy_pages_async = functools.partial(
    jax.jit, static_argnums=(0,))(_copy_pages_impl)


# ---------------------------------------------------- mesh validation

def _validate_tp(cfg, mesh, tp_axis: str, tp_mode: str, params) -> int:
    """Tensor-parallel admissibility checks (whole heads per shard;
    int4-packed row shards hold whole bytes). Returns tp size."""
    from repro.core.qlinear import iter_qlinear

    if cfg.n_experts:
        raise NotImplementedError("mesh serving covers the dense "
                                  "(non-MoE) family")
    tp = mesh.shape[tp_axis]
    packed = any(l.packed for _, l in iter_qlinear(params))
    unit = 2 * tp if (packed and tp_mode == "psum") else tp
    for dim, name in ((cfg.n_heads, "n_heads"),
                      (cfg.n_kv_heads, "n_kv_heads")):
        if dim % tp:
            raise ValueError(
                f"{name}={dim} must divide by {tp_axis}={tp} (whole "
                f"heads per shard)")
    for dim, name in ((cfg.q_dim, "q_dim"), (cfg.d_ff, "d_ff")):
        if dim % unit:
            raise ValueError(
                f"{name}={dim} must divide by {unit} "
                f"({tp_axis}={tp}"
                + (", ×2: int4-packed row shards hold whole bytes)"
                   if unit != tp else ")"))
    return tp


# --------------------------------------------------------- ragged executor

class RaggedExecutor:
    """The unified token-budget step: one ragged model invocation per
    engine step over the flat packed token batch (see module docstring
    and ``scheduler.TokenBudgetScheduler.pack``)."""

    def __init__(self, model, params, cache, *, n_slots: int = 1,
                 paged_kernel: bool = False,
                 mesh=None, tp_axis: str = "model",
                 tp_mode: str = "gather", tp_kernels: bool = False,
                 draft=None, spec_k: int = 0, donate: bool = True):
        if model.ragged_step is None:
            raise NotImplementedError(
                f"family {getattr(model.cfg, 'family', '?')!r} has no "
                f"ragged (unified-step) forward")
        self.model, self.params, self.cache = model, params, cache
        # donate=False picks the non-donating executables so dispatch
        # stays asynchronous on XLA:CPU (which runs donated computations
        # inline) — the pipelined engine's requirement; costs one pool-
        # sized output buffer per step instead of the in-place update.
        # The shard_mapped mesh step is non-donating either way.
        self._donate = bool(donate)
        self.n_slots = n_slots
        self.paged_kernel = paged_kernel
        self.mesh = mesh
        self.n_dispatch = 0     # device calls issued (hot-loop accounting)
        # the engine cycle of the next step()/decode_step(): the engine
        # sets it; the exec.* spans carry it as step=
        self.step_idx = 0
        # previous step's device-resident token vector (the injection
        # source for rows packed before their fed token was observed —
        # pipelined serving). None until the first step; chained by
        # step()/decode_step(). Widths coincide across the two step
        # kinds: the ragged vector is n_slots*(spec_k+1) wide and the
        # fused-decode vector n_slots wide, and the fast path only
        # engages at spec_k == 0.
        self._prev = None
        # speculative draft side: (model, params, cache) over a parallel
        # paged pool. Always plain-jit (never shard_mapped): only the
        # TARGET verify pass determines output tokens, so draft numerics
        # need determinism, not tp-identity — under a mesh the draft
        # runs replicated on the default device.
        self.spec_k = spec_k
        if draft is not None:
            self.draft_model, self.draft_params, self.draft_cache = draft
        else:
            self.draft_model = self.draft_params = self.draft_cache = None
        # pure-decode fast path (see _fused_decode_step): one stable
        # callable per executor so the jit cache keys on it once
        self._decode_fn = None
        if mesh is None and paged_kernel and model.decode is not None:
            self._decode_fn = (
                lambda p, t, c: model.decode(p, t, c, paged_kernel=True))
        if mesh is not None:
            self._init_mesh(mesh, tp_axis, tp_mode, tp_kernels)

    def _init_mesh(self, mesh, tp_axis: str, tp_mode: str,
                   tp_kernels: bool) -> None:
        """Unified step under shard_map: pools shard the head axis on
        ``model`` (the page axis stays whole); the host-built
        descriptors (packed tokens, positions, page-table rows, logit
        rows, kernel query blocks) all replicate
        (``distributed.sharding.ragged_desc_specs``)."""
        from jax.sharding import PartitionSpec as P

        from repro.distributed.compat import shard_map
        from repro.distributed import sharding as shlib

        cfg = self.model.cfg
        _validate_tp(cfg, mesh, tp_axis, tp_mode, self.params)
        for a in ("data", "pod"):
            if a in mesh.axis_names and mesh.shape[a] > 1:
                raise NotImplementedError(
                    "unified serving is tensor-parallel only (the paged "
                    "pool is a global allocation) — use a (1, tp) mesh")
        pspecs = shlib.tp_param_specs(self.params, mesh, axis=tp_axis,
                                      cfg=cfg, row_mode=tp_mode)
        cspecs = shlib.tp_cache_specs(self.cache, mesh, axis=tp_axis)
        self.params = jax.device_put(self.params, shlib.named(pspecs, mesh))
        self.cache = jax.device_put(self.cache, shlib.named(cspecs, mesh))
        cdict = dict(cspecs, pos=P(None), page_table=P(None, None))
        model = self.model
        pk = self.paged_kernel
        tp_kw = dict(tp_axis=tp_axis, tp_mode=tp_mode, tp_kernels=tp_kernels)

        # the step returns the (R,) greedy token ids instead of logits:
        # the logits are replicated across the tp shards (tp_mode
        # "gather" materializes the full vocab row on every shard), so
        # the in-shard argmax is replicated too — device-resident
        # sampling with bitwise tp-identical tokens. prev_toks/tok_src
        # (pipelined token injection) replicate like the descriptors.
        if pk:
            desc_specs = shlib.ragged_desc_specs(
                {k: jax.ShapeDtypeStruct((1, 1), jnp.int32)
                 for k in ("qidx", "qpos", "table")}
                | {k: jax.ShapeDtypeStruct((1,), jnp.int32)
                   for k in ("lengths", "inv_seq", "inv_qi")})

            def rag(p, t, c, lr, rd, prev, src):
                t = _inject_prev(t, prev, src)
                return model.ragged_step(p, t, c, lr, paged_kernel=True,
                                         ragged_desc=rd, greedy=True,
                                         **tp_kw)

            in_specs = (pspecs, P(None, None), cdict, P(None), desc_specs,
                        P(None), P(None))
        else:
            def rag(p, t, c, lr, prev, src):
                t = _inject_prev(t, prev, src)
                return model.ragged_step(p, t, c, lr, greedy=True, **tp_kw)

            in_specs = (pspecs, P(None, None), cdict, P(None),
                        P(None), P(None))
        self._mesh_step = jax.jit(shard_map(
            rag, mesh=mesh, in_specs=in_specs,
            out_specs=(P(None), cdict), check_vma=False))

    def _prev_arr(self, width: int):
        """The previous step's device token vector (injection source),
        or inert zeros before the first step / after a reset."""
        if self._prev is None or self._prev.shape[0] != width:
            self._prev = jnp.zeros((width,), jnp.int32)
        return self._prev

    def reset_pipeline(self) -> None:
        """Forget the previous step's device tokens (engine reset):
        a fresh run must not inject a stale vector. Injection is already
        structurally dead for fresh sequences (tok_src = -1), so this is
        defense in depth."""
        self._prev = None

    def step(self, packed: dict):
        """Run one packed unified step; returns the greedy token ids at
        the packed logit rows as a DEVICE (R,) int32 array (only the
        first ``packed['n_logits']`` entries are real) — sampling runs
        inside the jitted step and the call does NOT block, so a
        pipelined caller can keep packing while the step executes.
        Synchronous callers block + ``np.asarray`` the result
        themselves."""
        with span("exec.step", step=self.step_idx):
            self.n_dispatch += 1
            tokens = jnp.asarray(packed["tokens"])
            pos = jnp.asarray(packed["pos"])
            ptab = jnp.asarray(packed["page_table"])
            lrows = jnp.asarray(packed["logit_rows"])
            prev = self._prev_arr(lrows.shape[0])
            src = jnp.asarray(packed["tok_src"])
            desc = packed.get("ragged_desc")
            if desc is not None:
                desc = {k: jnp.asarray(v) for k, v in desc.items()}
            if self.mesh is None:
                fn = _unified_step if self._donate else _unified_step_async
                toks, self.cache = fn(
                    self.model.ragged_step, self.paged_kernel, True,
                    self.params, self.cache, tokens, pos, ptab, lrows, desc,
                    prev, src)
            else:
                cache = dict(self.cache, pos=pos, page_table=ptab)
                if self.paged_kernel:
                    toks, cache = self._mesh_step(self.params, tokens, cache,
                                                  lrows, desc, prev, src)
                else:
                    toks, cache = self._mesh_step(self.params, tokens, cache,
                                                  lrows, prev, src)
                cache.pop("pos")
                cache.pop("page_table")
                self.cache = cache
            self._prev = toks
            return toks

    @property
    def supports_decode_step(self) -> bool:
        """True when decode-only plans may dispatch via ``decode_step``."""
        return self._decode_fn is not None

    def decode_step(self, tokens: np.ndarray, pos: np.ndarray,
                    table: np.ndarray, tok_src=None):
        """One batched decode over the compact (n_slots, 1) layout — the
        pure-decode fast path (see ``_fused_decode_step``). Non-decoding
        slots carry a dummy token at position 0 against the null table
        row (inert writes, discarded outputs). Returns the greedy token
        per slot as a DEVICE (n_slots,) int32 array without blocking
        (see ``step``)."""
        with span("exec.decode_step", step=self.step_idx):
            self.n_dispatch += 1
            if tok_src is None:
                tok_src = np.full((self.n_slots,), -1, np.int32)
            prev = self._prev_arr(self.n_slots)
            fn = (_fused_decode_step if self._donate
                  else _fused_decode_step_async)
            toks, self.cache = fn(
                self._decode_fn, self.params, self.cache,
                jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(table),
                prev, jnp.asarray(tok_src))
            self._prev = toks
            return toks

    def copy_pages(self, pairs) -> None:
        """Device-copy each (src, dst) page pair in ONE dispatch (issued
        strictly before the step that writes past the shared boundary —
        dispatch order is device order on a stream, so even a src freed
        and reallocated within the same plan is read before its new
        owner writes it)."""
        if not pairs:
            return
        copy_fn = self.model.copy_paged_pages
        if copy_fn is None:
            raise NotImplementedError(
                f"family {getattr(self.model.cfg, 'family', '?')!r} has "
                f"no paged-pool page copy (copy_paged_pages)")
        self.n_dispatch += 1
        width = 1 << (max(len(pairs), self.n_slots) - 1).bit_length()
        src = np.zeros((width,), np.int32)
        dst = np.zeros((width,), np.int32)
        for i, (s, d) in enumerate(pairs):
            src[i], dst[i] = s, d
        fn = _copy_pages if self._donate else _copy_pages_async
        self.cache = fn(copy_fn, self.cache, jnp.asarray(src),
                        jnp.asarray(dst))

    # ---------------------------------------------------- speculative draft

    def draft_prefill(self, packed: dict) -> None:
        """Write one packed draft-prefill step's KV into the draft pool
        (same ragged shape as ``step``, draft params/pool, logits
        discarded). Plain jit even under a mesh — a separate compile
        keyed on the draft model's ``ragged_step``."""
        self.n_dispatch += 1
        fn = _unified_step if self._donate else _unified_step_async
        _, self.draft_cache = fn(
            self.draft_model.ragged_step, False, False, self.draft_params,
            self.draft_cache, jnp.asarray(packed["tokens"]),
            jnp.asarray(packed["pos"]),
            jnp.asarray(packed["page_table"]),
            jnp.asarray(packed["logit_rows"]), None, None, None)

    def draft_k(self, tok0: np.ndarray, pos0: np.ndarray,
                table: np.ndarray, tok_src=None) -> np.ndarray:
        """Propose ``spec_k + 1`` greedy tokens per slot in ONE dispatch
        (``_draft_scan``); returns them as (spec_k + 1, n_slots) numpy.
        The scan feeds each slot's argmax back at the next position, so
        the draft pool ends the call holding KV for every proposed
        position — including the extra row the bonus-token case needs.
        ``tok_src`` (pipelined mode) injects in-flight base tokens from
        the previous TARGET step's device vector; the blocking fetch of
        the drafts therefore also waits out that step — speculative
        cycles overlap only their pack/observe host work."""
        self.n_dispatch += 1
        if tok_src is None:
            prev, src = None, None
        else:
            prev = self._prev_arr(self.n_slots * (self.spec_k + 1))
            src = jnp.asarray(tok_src)
        fn = _draft_scan if self._donate else _draft_scan_async
        drafts, self.draft_cache = fn(
            self.draft_model.decode, self.spec_k + 1, self.draft_params,
            self.draft_cache, jnp.asarray(tok0), jnp.asarray(pos0),
            jnp.asarray(table), prev, src)
        return np.asarray(jax.block_until_ready(drafts))
