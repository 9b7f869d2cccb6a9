"""Continuous-batching serving engine over a paged KV-cache.

The serving loop the roofline attribution instruments (docs/DESIGN.md
§15): requests arrive on a tick clock, wait in a bounded FIFO queue, and
are admitted into one of ``n_slots`` sequence slots backed by the shared
:class:`~repro.serve.paged_kv.PagedKVCache` page pool.  Each engine tick

1. admits queue heads while a slot *and* enough free pages exist (FIFO —
   the head blocks, so admission order is arrival order),
2. advances every prefilling slot by one prompt chunk (chunked prefill
   *interleaved* with decode — long prompts never stall running decodes
   for more than one chunk's latency),
3. runs one batched decode step over all decoding slots,
4. retires finished sequences (EOS / ``max_new`` / context-full),
   returning their pages to the free-list the same tick.

Three compiled executables, each lowered once through the shared
``repro.core.profiler.compile_fn`` so the object the engine *times* is
the object the trace layer *analyzes* (the repo's one-compile rule):

* ``prefill_first(params, chunk, valid, pools, coords)`` — the start-of-
  prompt chunk: causal self-attention over the chunk only; under
  ``fusion="auto"`` this is the chunked-prefill seam that routes to the
  flash kernel when eligible (PR 4's ``flash_from_chunked_eligible``);
* ``prefill_ext(params, chunk, start, valid, pools, page_row, coords)``
  — later chunks: gathers the slot's paged context dense, attends the
  chunk against context + itself;
* ``decode(params, tokens, pools, table, lengths, coords)`` — one token
  for every slot: gather pages → dense ``DecodeState`` →
  ``model.decode_fn`` → scatter the new K/V back to the pool (inactive
  slots carry page id ``-1``, so their writes drop).

Faults degrade gracefully: empty prompts, prompts past ``max_len`` and
queue overflow are rejected with a reason; mid-stream cancellation frees
the slot and pages immediately; pool exhaustion finishes the sequence
``truncated`` instead of wedging the engine.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.models.api import Model, build
from repro.resilience import faults
from repro.serve.paged_kv import DEFAULT_PAGE_SIZE, PagedKVCache

#: families the engine can serve: token-only prompts + a paged KV cache
SERVABLE_FAMILIES = ("dense", "moe")

#: phase each compiled executable's wall time lands in
PHASE_OF = {"prefill_first": "prefill", "prefill_ext": "prefill",
            "decode": "decode"}


@dataclasses.dataclass
class Request:
    """One user request; the engine fills the tracking fields in."""

    uid: int
    prompt: np.ndarray                # (len,) int32
    max_new: int = 16
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    arrival: int = 0                  # arrival tick (virtual clock)
    status: str = "new"               # new|queued|active|done|rejected|cancelled
    finish_reason: str | None = None  # length|eos|truncated|... when done
    admit_tick: int | None = None
    first_tick: int | None = None
    done_tick: int | None = None
    t_arrival: float | None = None    # wall-clock stamps (metrics)
    t_first: float | None = None
    t_done: float | None = None


@dataclasses.dataclass
class _Slot:
    """One active sequence: its request + prefill progress + next token."""

    req: Request
    phase: str                        # "prefill" | "decode"
    filled: int = 0                   # prompt tokens prefilled so far
    next_tok: int = 0


class Engine:
    """Continuous-batching engine over a transformer-family model."""

    def __init__(self, cfg: ModelConfig, run: RunConfig, params: Any,
                 n_slots: int = 4, max_len: int = 256,
                 eos_id: int | None = None,
                 page_size: int = DEFAULT_PAGE_SIZE,
                 n_pages: int | None = None,
                 prefill_chunk: int | None = None,
                 queue_capacity: int | None = None,
                 tick_retries: int = 2):
        if cfg.family not in SERVABLE_FAMILIES:
            raise ValueError(
                f"Engine serves token-prompt KV-cache families "
                f"{SERVABLE_FAMILIES}; got {cfg.family!r} "
                "(vlm needs prefix embeddings, ssm/hybrid carry "
                "recurrent state — decode those via repro.models.api)")
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.cfg, self.run, self.params = cfg, run, params
        self.n_slots, self.max_len, self.eos_id = n_slots, max_len, eos_id
        self.chunk = min(prefill_chunk or 32, max_len)
        self.queue_capacity = queue_capacity
        self.model: Model = build(cfg)
        self.cache = PagedKVCache(cfg, n_slots, max_len,
                                  page_size=page_size, n_pages=n_pages)
        self._slots: list[_Slot | None] = [None] * n_slots
        self.queue: deque[Request] = deque()
        self.tick_count = 0
        self.tick_retries = tick_retries
        self.retried_ticks = 0
        # per-executable timing accumulators (the trace layer's input)
        self.wall = {name: 0.0 for name in PHASE_OF}
        self.calls = {name: 0 for name in PHASE_OF}
        self._compiled: dict[str, Any] = {}

    # ------------------------------------------------------------------
    # compiled executables (lazy; one compile each, shared with analysis)
    # ------------------------------------------------------------------

    def executable(self, name: str):
        if name not in self._compiled:
            build_fn = getattr(self, f"_build_{name}")
            self._compiled[name] = build_fn()
        return self._compiled[name]

    def warmup(self) -> None:
        """Compile every executable now.  Compilation is set-up: left to
        the first request, it would land in that request's time to first
        token."""
        for name in PHASE_OF:
            self.executable(name)

    def _timed(self, name: str, *args):
        fn = self.executable(name)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        self.wall[name] += time.perf_counter() - t0
        self.calls[name] += 1
        return out

    def _prefill_body(self, params, chunk, start, valid, k_pool, v_pool,
                      attend, wpage, woff):
        """Shared chunk-prefill math: the residual stream of ``chunk``
        (C,) evolved layer by layer with exactly ``block_apply``'s op
        sequence (norm → attention → residual-norm seam → mlp/moe →
        residual), with attention delegated to ``attend(qg, k, v, kp,
        vp)`` and the chunk's per-layer K/V scattered to the page pool
        at ``(wpage, woff)`` (``-1`` page ids drop — padding mask).
        """
        from repro.models import layers as L
        from repro.models import moe as MOE

        cfg, run = self.cfg, self.run
        C = chunk.shape[0]
        cd = run.compute_dtype
        H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        G = H // K
        positions = start + jnp.arange(C)

        x = L.embed_apply(params["embed"], chunk[None], run)     # (1, C, D)

        def body(h, inp):
            layer_p, kp, vp = inp               # kp: (n_pages, page, K, hd)
            xn = L.rmsnorm_apply(layer_p["ln_attn"], h, cfg.norm_eps, run)
            xc = xn.astype(cd)
            q = jnp.einsum("bsd,dhk->bshk", xc,
                           layer_p["attn"]["wq"].astype(cd))
            k = jnp.einsum("bsd,dhk->bshk", xc,
                           layer_p["attn"]["wk"].astype(cd))
            v = jnp.einsum("bsd,dhk->bshk", xc,
                           layer_p["attn"]["wv"].astype(cd))
            q = L.rope(q, positions, cfg.rope_theta)
            k = L.rope(k, positions, cfg.rope_theta)
            qg = q.reshape(1, C, K, G, hd)
            out = attend(qg, k, v, kp, vp)
            attn = out.reshape(1, C, H, hd)
            y = jnp.einsum("bshk,hkd->bsd", attn,
                           layer_p["attn"]["wo"].astype(cd)).astype(h.dtype)
            h2, z = L.rmsnorm_residual_apply(layer_p["ln_mlp"], h, y,
                                             cfg.norm_eps, run)
            if cfg.family == "moe":
                z, _ = MOE.moe_apply(layer_p["moe"], z, cfg, run)
            else:
                z = L.mlp_apply(layer_p["mlp"], z, cfg, run)
            kp = kp.at[wpage, woff].set(k[0].astype(kp.dtype), mode="drop")
            vp = vp.at[wpage, woff].set(v[0].astype(vp.dtype), mode="drop")
            return h2 + z, (kp, vp)

        x, (k_pool, v_pool) = jax.lax.scan(
            body, x, (params["blocks"], k_pool, v_pool))
        x = L.rmsnorm_apply(params["ln_f"], x, cfg.norm_eps, run)
        last = jax.lax.dynamic_index_in_dim(x, valid - 1, axis=1,
                                            keepdims=True)       # (1, 1, D)
        logits = L.unembed_apply(params["embed"], last, run)[0, 0]   # (V,)
        return logits, k_pool, v_pool

    def _build_prefill_first(self):
        """Start-of-prompt chunk: causal self-attention over the chunk
        only — the flash-routable shape.  With fusion enabled an eligible
        chunk routes to the flash kernel (the PR 4 chunked → flash seam;
        ``fusion="auto"`` additionally asks the measured dispatch table);
        otherwise the masked reference sdpa runs."""
        from repro.core.profiler import compile_fn
        from repro.kernels.fused import ops as fops
        from repro.models import layers as L

        C = self.chunk
        cfg, run = self.cfg, self.run
        cd = run.compute_dtype
        sd = jnp.float32 if run.softmax_f32 else cd
        K, hd = cfg.n_kv_heads, cfg.head_dim
        G = cfg.n_heads // K
        use_flash = (fops.fusion_enabled(run)
                     and fops.use_flash_from_chunked(
                         run, (1, C, K, G, hd), (1, C, K, hd), cd,
                         causal=True, has_memory=False, has_cache=False,
                         softmax_f32=run.softmax_f32,
                         chunk=run.attn_chunk))
        self.prefill_first_flash = use_flash

        def fn(params, chunk, valid, k_pool, v_pool, wpage, woff):
            positions = jnp.arange(C)

            def attend(qg, k, v, kp, vp):
                # padded tail keys sit at positions >= valid, which the
                # causal mask already hides from every valid query — so
                # the plain-causal flash kernel needs no k_len mask here
                if use_flash:
                    from repro.kernels.flash_attention import ops as fa_ops
                    return fa_ops.flash_attention_gqa(
                        qg, k.astype(cd), v.astype(cd))
                return L._sdpa(qg, k.astype(cd), v.astype(cd),
                               positions, positions, causal=True,
                               k_len=valid, stat_dtype=sd)

            return self._prefill_body(params, chunk, jnp.int32(0), valid,
                                      k_pool, v_pool, attend, wpage, woff)

        return compile_fn(fn, args=self._prefill_args(ext=False))

    def _build_prefill_ext(self):
        """Later chunks: gather the slot's paged context dense, attend
        the chunk against context + itself (causal, length-masked)."""
        from repro.core.profiler import compile_fn
        from repro.models import layers as L

        C = self.chunk
        S_pad = self.cache.padded_len
        run = self.run
        cd = run.compute_dtype
        sd = jnp.float32 if run.softmax_f32 else cd

        def fn(params, chunk, start, valid, k_pool, v_pool, page_row,
               wpage, woff):
            pos = start + jnp.arange(C)

            def attend(qg, k, v, kp, vp):
                # this slot's paged context, dense: (S_pad, K, hd)
                ctxk = jnp.take(kp, page_row.clip(0), axis=0)
                ctxv = jnp.take(vp, page_row.clip(0), axis=0)
                ctxk = ctxk.reshape(S_pad, *ctxk.shape[2:])
                ctxv = ctxv.reshape(S_pad, *ctxv.shape[2:])
                # overlay the chunk's own fresh K/V (scatter; OOB drops)
                ctxk = ctxk.at[pos].set(k[0].astype(ctxk.dtype),
                                        mode="drop")
                ctxv = ctxv.at[pos].set(v[0].astype(ctxv.dtype),
                                        mode="drop")
                return L._sdpa(qg, ctxk[None].astype(cd),
                               ctxv[None].astype(cd), pos,
                               jnp.arange(S_pad), causal=True,
                               k_len=start + valid, stat_dtype=sd)

            return self._prefill_body(params, chunk, start, valid,
                                      k_pool, v_pool, attend, wpage, woff)

        return compile_fn(fn, args=self._prefill_args(ext=True))

    def _build_decode(self):
        """One batched decode tick: paged gather → dense DecodeState →
        ``model.decode_fn`` → scatter the new K/V back."""
        from repro.core.profiler import compile_fn
        from repro.models.transformer import DecodeState

        B = self.n_slots
        S_pad = self.cache.padded_len

        def fn(params, tokens, k_pool, v_pool, table, lengths, wpage, woff):
            dense_k = jnp.take(k_pool, table.clip(0), axis=1)
            dense_v = jnp.take(v_pool, table.clip(0), axis=1)
            L_ = dense_k.shape[0]
            dense_k = dense_k.reshape(L_, B, S_pad, *dense_k.shape[4:])
            dense_v = dense_v.reshape(L_, B, S_pad, *dense_v.shape[4:])
            state = DecodeState(k=dense_k, v=dense_v, length=lengths)
            logits, new_state = self.model.decode_fn(
                params, {"tokens": tokens}, state, self.run)
            bidx = jnp.arange(B)
            new_k = new_state.k[:, bidx, lengths]          # (L, B, K, hd)
            new_v = new_state.v[:, bidx, lengths]
            k_pool = k_pool.at[:, wpage, woff].set(
                new_k.astype(k_pool.dtype), mode="drop")
            v_pool = v_pool.at[:, wpage, woff].set(
                new_v.astype(v_pool.dtype), mode="drop")
            return logits[:, 0], k_pool, v_pool

        i32 = jnp.int32
        P = self.cache.pages_per_slot
        args = (self.params,
                jax.ShapeDtypeStruct((B, 1), i32),
                self.cache.k_pool, self.cache.v_pool,
                jax.ShapeDtypeStruct((B, P), i32),
                jax.ShapeDtypeStruct((B,), i32),
                jax.ShapeDtypeStruct((B,), i32),
                jax.ShapeDtypeStruct((B,), i32))
        return compile_fn(fn, args=args)

    def _prefill_args(self, ext: bool):
        i32 = jnp.int32
        C = self.chunk
        base = [self.params, jax.ShapeDtypeStruct((C,), i32)]
        if ext:
            base.append(jax.ShapeDtypeStruct((), i32))      # start
        base += [jax.ShapeDtypeStruct((), i32),             # valid
                 self.cache.k_pool, self.cache.v_pool]
        if ext:
            base.append(jax.ShapeDtypeStruct(
                (self.cache.pages_per_slot,), i32))         # page_row
        base += [jax.ShapeDtypeStruct((C,), i32),           # wpage
                 jax.ShapeDtypeStruct((C,), i32)]           # woff
        return tuple(base)

    # ------------------------------------------------------------------
    # admission / faults
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Queue one request; False = rejected (reason on the request)."""
        if req.t_arrival is None:
            req.t_arrival = time.perf_counter()
        if len(req.prompt) == 0:
            req.status, req.finish_reason = "rejected", "empty_prompt"
            return False
        if len(req.prompt) > self.max_len:
            req.status, req.finish_reason = "rejected", "prompt_too_long"
            return False
        if (self.queue_capacity is not None
                and len(self.queue) >= self.queue_capacity):
            req.status, req.finish_reason = "rejected", "queue_full"
            return False
        req.status = "queued"
        self.queue.append(req)
        return True

    def cancel(self, uid: int) -> bool:
        """Cancel a queued or running request; its pages free immediately."""
        for req in list(self.queue):
            if req.uid == uid:
                self.queue.remove(req)
                req.status, req.finish_reason = "cancelled", "cancelled"
                req.done = True
                return True
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.req.uid == uid:
                slot.req.status = "cancelled"
                slot.req.finish_reason = "cancelled"
                slot.req.done = True
                self._release(i)
                return True
        return False

    def _release(self, slot_idx: int) -> None:
        self.cache.release(slot_idx)
        self._slots[slot_idx] = None

    def _finish(self, slot_idx: int, reason: str) -> None:
        req = self._slots[slot_idx].req
        req.status, req.finish_reason, req.done = "done", reason, True
        req.done_tick = self.tick_count
        req.t_done = time.perf_counter()
        self._release(slot_idx)

    def _admit_from_queue(self) -> None:
        """FIFO head-of-line admission: a slot plus enough free pages."""
        while self.queue:
            req = self.queue[0]
            free = [i for i, s in enumerate(self._slots) if s is None]
            if not free:
                return
            slot = free[0]
            if not self.cache.alloc(slot, len(req.prompt)):
                return                      # head waits for pages (FIFO)
            self.queue.popleft()
            req.status = "active"
            req.admit_tick = self.tick_count
            self._slots[slot] = _Slot(req=req, phase="prefill", filled=0)

    # ------------------------------------------------------------------
    # the tick
    # ------------------------------------------------------------------

    def _prefill_chunk(self, slot_idx: int, prompt: np.ndarray,
                       start: int):
        """Run the prompt chunk at ``start`` for an allocated slot,
        writing its K/V to the pool → (last-position logits, tokens)."""
        valid = min(self.chunk, len(prompt) - start)
        chunk = np.zeros(self.chunk, np.int32)
        chunk[:valid] = prompt[start:start + valid]
        wpage, woff = self.cache.write_coords(slot_idx, start, self.chunk)
        # positions past the valid token count never land in the pool
        wpage[valid:] = -1
        i32 = jnp.int32
        if start == 0:
            logits, kp, vp = self._timed(
                "prefill_first", self.params, jnp.asarray(chunk),
                i32(valid), self.cache.k_pool, self.cache.v_pool,
                jnp.asarray(wpage), jnp.asarray(woff))
        else:
            logits, kp, vp = self._timed(
                "prefill_ext", self.params, jnp.asarray(chunk),
                i32(start), i32(valid), self.cache.k_pool,
                self.cache.v_pool,
                jnp.asarray(self.cache.page_table[slot_idx]),
                jnp.asarray(wpage), jnp.asarray(woff))
        self.cache.k_pool, self.cache.v_pool = kp, vp
        self.cache.lengths[slot_idx] = start + valid
        return logits, valid

    def _prefill_step(self, slot_idx: int) -> None:
        """Advance one prefilling slot by one prompt chunk."""
        slot = self._slots[slot_idx]
        req = slot.req
        prompt = np.asarray(req.prompt, np.int32)
        logits, valid = self._prefill_chunk(slot_idx, prompt, slot.filled)
        slot.filled += valid
        if slot.filled < len(prompt):
            return                          # more chunks next tick
        # prompt complete: the chunk's last logits give the first token
        tok = int(np.argmax(np.asarray(logits[:self.cfg.vocab_size])))
        req.out.append(tok)
        req.first_tick = self.tick_count
        req.t_first = time.perf_counter()
        slot.next_tok = tok
        slot.phase = "decode"
        self._maybe_finish(slot_idx, tok)

    def prompt_logits(self, prompt) -> jax.Array:
        """Last-position logits of ``prompt`` through the chunked-prefill
        executables every request's first token comes from.  Runs in a
        free slot, whose pages are released again before returning."""
        prompt = np.asarray(prompt, np.int32)
        slot = self._slots.index(None)       # ValueError: no free slot
        if not 0 < len(prompt) <= self.max_len \
                or not self.cache.alloc(slot, len(prompt)):
            raise ValueError(f"cannot prefill a {len(prompt)}-token "
                             "prompt here")
        try:
            start = 0
            while start < len(prompt):
                logits, valid = self._prefill_chunk(slot, prompt, start)
                start += valid
        finally:
            self.cache.release(slot)
        return logits

    def _maybe_finish(self, slot_idx: int, tok: int) -> None:
        """Completion checks after a token landed; frees the slot."""
        slot = self._slots[slot_idx]
        req = slot.req
        if self.eos_id is not None and tok == self.eos_id:
            self._finish(slot_idx, "eos")
        elif len(req.out) >= req.max_new:
            self._finish(slot_idx, "length")
        elif int(self.cache.lengths[slot_idx]) >= self.max_len:
            # no room to write the next input token's K/V
            self._finish(slot_idx, "truncated")

    def _decode_step(self) -> None:
        """One batched decode over every decoding slot."""
        active = [i for i, s in enumerate(self._slots)
                  if s is not None and s.phase == "decode"]
        # pool pressure: growing past a page boundary may fail on an
        # undersized pool — finish those sequences truncated, pre-decode
        for i in list(active):
            if not self.cache.alloc(i, int(self.cache.lengths[i]) + 1):
                self._finish(i, "truncated")
                active.remove(i)
        if not active:
            return
        B = self.n_slots
        tokens = np.zeros((B, 1), np.int32)
        wpage = np.full(B, -1, np.int32)
        woff = np.zeros(B, np.int32)
        for i in active:
            slot = self._slots[i]
            tokens[i, 0] = slot.next_tok
            pg, of = self.cache.write_coords(i, int(self.cache.lengths[i]),
                                             1)
            wpage[i], woff[i] = pg[0], of[0]
        logits, kp, vp = self._timed(
            "decode", self.params, jnp.asarray(tokens),
            self.cache.k_pool, self.cache.v_pool,
            self.cache.table_device(),
            jnp.asarray(self.cache.lengths.astype(np.int32)),
            jnp.asarray(wpage), jnp.asarray(woff))
        self.cache.k_pool, self.cache.v_pool = kp, vp
        toks = np.argmax(np.asarray(logits)[:, :self.cfg.vocab_size],
                         axis=-1)
        for i in active:
            slot = self._slots[i]
            self.cache.lengths[i] += 1
            tok = int(toks[i])
            slot.req.out.append(tok)
            slot.next_tok = tok
            self._maybe_finish(i, tok)

    def tick(self) -> None:
        """One engine step: admit → prefill chunks → decode → retire."""
        faults.active_plan().maybe_raise("serve_fault",
                                        target=self.tick_count)
        self._admit_from_queue()
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.phase == "prefill":
                self._prefill_step(i)
        self._decode_step()
        self.tick_count += 1

    def _tick_resilient(self) -> None:
        """``tick`` with bounded retry on transient faults.

        The fault hook fires before any admission or cache mutation, so
        a retried tick replays cleanly from the same engine state.
        """
        for attempt in range(self.tick_retries + 1):
            try:
                return self.tick()
            except faults.TransientFault:
                if attempt >= self.tick_retries:
                    raise
                self.retried_ticks += 1

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self._slots)

    # ------------------------------------------------------------------
    # drivers
    # ------------------------------------------------------------------

    def run_trace(self, requests: list[Request], max_ticks: int = 4096):
        """Serve an arrival trace to completion; returns ServeStats.

        Requests are submitted when the tick clock reaches their
        ``arrival``; rejected ones stay rejected (reason on the request).
        """
        from repro.serve.metrics import stats_from_requests

        self.warmup()
        t0 = time.perf_counter()
        start_tick = self.tick_count
        pending = sorted(requests, key=lambda r: r.arrival)
        i = 0
        while self.tick_count - start_tick < max_ticks:
            while i < len(pending) \
                    and pending[i].arrival <= self.tick_count:
                self.submit(pending[i])
                i += 1
            if i == len(pending) and not self.queue \
                    and self.n_active == 0:
                break
            self._tick_resilient()
        prefill_wall = (self.wall["prefill_first"]
                        + self.wall["prefill_ext"])
        return stats_from_requests(
            requests, wall_s=time.perf_counter() - t0,
            ticks=self.tick_count - start_tick,
            prefill_wall_s=prefill_wall,
            decode_wall_s=self.wall["decode"])

    def serve(self, requests: list[Request], max_ticks: int = 512
              ) -> list[Request]:
        """Back-compat driver: serve a list to completion, return it."""
        self.run_trace(requests, max_ticks=max_ticks)
        return requests
