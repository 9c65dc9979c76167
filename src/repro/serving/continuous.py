"""Continuous-batching decode engine over the paged KV cache.

The serving hot path: a fixed set of decode *slots* advances one token
per step in a single jitted SPMD program; sequences join (bucketed
prefill + page scatter) and leave (eviction frees their pages) mid-
flight, so throughput tracks live tokens instead of the slowest member
of a static batch.  Three design rules:

* **Paged memory** — K/V live in a shared page pool addressed through
  per-slot block tables (``serving.paged_cache``); HBM scales with live
  tokens, admission is a free-list check, eviction is O(pages).
* **Fused sampling, donated state** — the decode step embeds, attends
  through the paged kernel, writes the new K/V, and samples (greedy or
  temperature) in ONE jitted call whose page pool is donated; the only
  per-step host traffic is the sampled-token fetch that the scheduler
  itself needs.
* **Bucketed prefill** — prompts pad to power-of-two buckets so joining
  costs one of O(log max_len) compiled programs, not one per length;
  the prompt's K/V is scattered into its pages page-aligned, and the
  first token is sampled inside the same jitted call.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models import layers, model_zoo
from repro.models.transformer import PagedKVState, run_layers_prefill
from repro.serving.paged_cache import BlockAllocator, PrefixCache, pages_for
from repro.serving.scheduler import (
    FASTPATH_COUNTERS,
    AdmissionScheduler,
    Request,
    RequestOutput,
    charged_can_admit,
    remaining_new_tokens,
)


@dataclasses.dataclass
class _ActiveSeq:
    """Host-side record for a sequence occupying a decode slot."""

    req: Request
    generated: list[int]
    token_times: list[float]
    # chunked-prefill progress: prompt tokens already in the cache (equals
    # the slot's seq_len until prefill completes); 0 on the legacy path
    prefill_pos: int = 0
    prefill_dur: float = 0.0
    queue_wait: float = -1.0  # negative = unknown (virtual clock)
    # admission found no shared prefix for this prompt: while its chunked
    # prefill is in flight, further cold admissions are deferred so
    # followers can hit the pages it registers on completion
    cold_prefill: bool = False

    @property
    def remaining(self) -> int:
        return self.req.max_new_tokens - len(self.generated)


def _ngram_propose(hist: np.ndarray, n: int, k: int) -> list[int]:
    """Prompt-lookup draft proposer: find an earlier occurrence of the
    sequence's length-``n`` suffix and return (up to) the ``k`` tokens
    that followed it.  Among the matches, prefer the most recent one with
    a *full* ``k``-token continuation on record; inside a repetition
    (where every recent match sits too close to the end to have one) fall
    back to the earliest match, which carries the longest known
    continuation — the difference between drafting 1 token and drafting
    ``k`` per step on constant runs.  Pure host-side numpy — drafts are
    free relative to a model step; a wrong draft costs nothing but its
    slice of the already-batched verification window."""
    L = int(hist.shape[0])
    if k <= 0 or L <= n:
        return []
    pat = hist[L - n:]
    win = np.lib.stride_tricks.sliding_window_view(hist, n)
    hits = np.flatnonzero((win == pat).all(axis=1))
    hits = hits[hits < L - n]  # exclude the suffix matching itself
    if hits.size == 0:
        return []
    full = hits[hits + n + k <= L]
    i = int(full[-1]) if full.size else int(hits[0])
    return [int(t) for t in hist[i + n: i + n + k]]


def _bucket_len(plen: int, page_size: int, max_len: int) -> int:
    """Smallest power-of-two >= plen, page-aligned and capped at max_len."""
    b = page_size
    while b < plen:
        b *= 2
    b = ((b + page_size - 1) // page_size) * page_size
    return min(b, ((max_len + page_size - 1) // page_size) * page_size)


class ContinuousBatchingEngine:
    """Slot-scheduled continuous batching for transformer-family models.

    SSM/hybrid state is slot-indexed differently and mrope needs
    per-request position streams; both fall back to ``ServeEngine``."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        *,
        num_slots: int = 8,
        page_size: int = 16,
        max_len: int = 512,
        num_pages: Optional[int] = None,
        seed: int = 0,
        on_stage: Optional[Callable[[str, dict], None]] = None,
        spec_k: int = 0,
        spec_ngram: int = 2,
        prefix_cache: bool = False,
        prefill_chunk: int = 0,
        device: Optional[jax.Device] = None,
    ):
        if cfg.family not in ("dense", "moe"):
            raise ValueError(f"paged serving supports dense/moe, got {cfg.family!r}")
        if cfg.rope_mode == "mrope":
            raise ValueError("paged serving supports standard/none rope")
        if spec_k < 0 or prefill_chunk < 0 or spec_ngram < 1:
            raise ValueError("spec_k/prefill_chunk must be >= 0, spec_ngram >= 1")
        self.cfg = cfg
        self.model = model_zoo.build_model(cfg)
        # the replica's chip: params and page pool live there, so every
        # program of this engine runs there (None: the default device)
        self.device = device
        self.params = params if device is None else jax.device_put(params, device)
        self.num_slots = num_slots
        self.page_size = page_size
        self.max_len = max_len
        self.max_pages_per_seq = pages_for(max_len, page_size)
        self.num_pages = num_pages or num_slots * self.max_pages_per_seq
        # disjoint sampling streams: decode folds the step counter, prefill
        # folds (rid, tokens-already-generated) — no key is ever shared
        # between the two, or between a preempted request's readmissions
        self._key = jax.random.PRNGKey(seed)
        self._decode_key = jax.random.fold_in(self._key, 0)
        self._prefill_key = jax.random.fold_in(self._key, 1)
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._prefill = jax.jit(self._prefill_impl, donate_argnums=(1,))
        # ---- serving fast path (all default-off; behavior is bit-identical
        # to the legacy path until a flag is enabled) ----
        self.spec_k = spec_k
        self.spec_ngram = spec_ngram
        self.prefix_cache = prefix_cache
        self.prefill_chunk = min(prefill_chunk, max_len)
        self.fastpath = bool(spec_k or prefix_cache or self.prefill_chunk)
        # fixed window widths so the step program compiles at most 3 shapes:
        # 1 (plain decode), 1+spec_k (speculative), max(...) (mixed prefill)
        self._q_decode = 1 + spec_k
        self._q_mixed = max(self._q_decode, self.prefill_chunk)
        self._multi = jax.jit(self._multi_impl, donate_argnums=(1,))
        # optional observability sink: called as on_stage("prefill"|"decode",
        # info) with wall durations; None costs nothing on the hot path
        self._on_stage = on_stage
        self.reset()

    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Fresh pool/queue/slots; compiled programs are retained."""
        with jax.default_device(self.device):
            self.pages = self.model.init_paged_state(
                self.num_pages + 1, self.page_size
            )
        self.alloc = BlockAllocator(
            self.num_slots, self.max_pages_per_seq, self.num_pages
        )
        self.scheduler = AdmissionScheduler()
        self._slots: list[Optional[_ActiveSeq]] = [None] * self.num_slots
        self._tokens = np.zeros((self.num_slots,), np.int32)
        self._temps = np.zeros((self.num_slots,), np.float32)
        self._counter = 0
        # outputs finished inside a step() that later raised; survives the
        # exception so a failing replica's router can still deliver them
        self._pending_outputs: list[RequestOutput] = []
        # fast-path state: the prefix index pins pages in the (fresh) alloc,
        # per-slot admitted prompts / token histories feed chunked prefill
        # and the n-gram proposer; counters surface through router stats
        self.prefix = (
            PrefixCache(self.alloc, self.page_size) if self.prefix_cache else None
        )
        self._prompts: list[Optional[np.ndarray]] = [None] * self.num_slots
        self._history: list[Optional[list[int]]] = [None] * self.num_slots
        self.counters: dict[str, int] = dict.fromkeys(FASTPATH_COUNTERS, 0)

    # ------------------------------------------------------------------
    # jitted programs
    # ------------------------------------------------------------------
    def _sample(self, logits: jax.Array, key, temps: jax.Array) -> jax.Array:
        """(B, V) logits + per-slot temperature -> (B,) int32 tokens."""
        lg = logits[:, : self.cfg.vocab_size].astype(jnp.float32)
        greedy = jnp.argmax(lg, axis=-1)
        safe = jnp.where(temps > 0, temps, 1.0)
        sampled = jax.random.categorical(key, lg / safe[:, None], axis=-1)
        return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)

    def _decode_impl(
        self, params, pages, tokens, block_tables, seq_lens, active, temps, key, step
    ):
        batch = {
            "tokens": tokens[:, None],
            "block_tables": block_tables,
            "seq_lens": seq_lens,
        }
        logits, pages = self.model.decode_step_paged(params, pages, batch)
        tok = self._sample(logits[:, -1], jax.random.fold_in(key, step), temps)
        return jnp.where(active, tok, tokens), pages

    def _prefill_impl(self, params, pages, tokens_pad, plen, page_ids, key, temp):
        """Prefill one prompt (padded to a bucket), scatter its K/V into the
        slot's pages, and sample the first token — one compiled program per
        bucket length."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        S = tokens_pad.shape[1]
        x = layers.embed_tokens(params["embed"], tokens_pad, dtype)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (1, S))
        angles = None if cfg.rope_mode == "none" else layers.rope_angles(cfg, pos)
        x, cache = run_layers_prefill(cfg, params["layers"], x, angles, pos, pos, S)
        # logits at the *real* last prompt position (padding sits above it and
        # is never attended by earlier positions under the causal mask)
        h = jax.lax.dynamic_slice_in_dim(x, plen - 1, 1, axis=1)
        h = layers.apply_norm(cfg, params["final_norm"], h)
        logits = layers.lm_logits(params["embed"], h, cfg.tie_embeddings)
        tok = self._sample(logits[:, -1], key, temp[None])[0]
        # page-aligned scatter: (L, S, kv, hd) -> (L, S/page, page, kv, hd);
        # pad pages beyond the prompt carry null ids and land in trash
        L, n = cache.k.shape[0], S // self.page_size
        kv_shape = (L, n, self.page_size) + cache.k.shape[3:]
        ks = cache.k[:, 0].reshape(kv_shape).astype(pages.k_pages.dtype)
        vs = cache.v[:, 0].reshape(kv_shape).astype(pages.v_pages.dtype)
        pages = PagedKVState(
            k_pages=pages.k_pages.at[:, page_ids].set(ks),
            v_pages=pages.v_pages.at[:, page_ids].set(vs),
        )
        return pages, tok

    def _multi_impl(
        self, params, pages, tokens, block_tables, seq_lens, temps, sample_idx,
        key, step,
    ):
        """Fast-path step: a Q-token window per slot (current token +
        speculative drafts, or a chunked-prefill slab) through one program.
        Returns per-position greedy argmax (B, Q) — the verifier — plus a
        temperature sample at each slot's ``sample_idx`` window position
        (its last real token), and the updated pool."""
        batch = {
            "tokens": tokens,
            "block_tables": block_tables,
            "seq_lens": seq_lens,
        }
        logits, pages = self.model.decode_step_paged(params, pages, batch)
        lg = logits[:, :, : self.cfg.vocab_size].astype(jnp.float32)  # (B, Q, V)
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        B = lg.shape[0]
        rows = lg[jnp.arange(B), sample_idx]  # (B, V)
        safe = jnp.where(temps > 0, temps, 1.0)
        sampled = jax.random.categorical(
            jax.random.fold_in(key, step), rows / safe[:, None], axis=-1
        )
        sampled = jnp.where(
            temps > 0, sampled, greedy[jnp.arange(B), sample_idx]
        ).astype(jnp.int32)
        return greedy, sampled, pages

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        # a continuation's prompt already contains its generated prefix, so
        # only the *remaining* budget counts against capacity
        gen_left = remaining_new_tokens(req)
        if gen_left < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must leave >= 1 to "
                "generate (prefill always samples the first token)"
            )
        total = req.prompt_len + gen_left
        if total > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt_len} + gen "
                f"{gen_left} exceeds max_len {self.max_len}"
            )
        # worst-case page need must fit the whole pool, or the request (or a
        # preempted continuation of it) could block the FCFS head forever
        if pages_for(total, self.page_size) > self.num_pages:
            raise ValueError(
                f"request {req.rid}: needs {pages_for(total, self.page_size)} "
                f"pages worst-case, pool has {self.num_pages}"
            )
        self.scheduler.submit(req)

    def _finish(self, slot: int, finished: list[RequestOutput]) -> None:
        s = self._slots[slot]
        finished.append(
            RequestOutput(
                rid=s.req.rid,
                prompt_len=s.req.prompt_len,
                tokens=s.generated,
                arrival_time=s.req.arrival_time,
                token_times=s.token_times,
                deadline_s=s.req.deadline_s,
            )
        )
        self.alloc.release(slot)
        self._slots[slot] = None
        self._temps[slot] = 0.0

    def _admit(self, now: float, finished: list[RequestOutput]) -> None:
        while True:
            req = self.scheduler.next_admissible(self.alloc, self.page_size, now)
            if req is None:
                return
            slot, page_ids = self.alloc.allocate_slot(req.prompt_len, self.page_size)
            plen = req.prompt_len
            bucket = _bucket_len(plen, self.page_size, self.max_len)
            tokens_pad = np.zeros((1, bucket), np.int32)
            tokens_pad[0, :plen] = req.tokens
            ids = np.full((bucket // self.page_size,), self.alloc.null_page, np.int32)
            ids[: len(page_ids)] = page_ids
            carry: _ActiveSeq = getattr(req, "_carry", None) or _ActiveSeq(
                req=req, generated=[], token_times=[]
            )
            key = jax.random.fold_in(
                jax.random.fold_in(self._prefill_key, req.rid), len(carry.generated)
            )
            pt0 = time.perf_counter()
            self.pages, tok = self._prefill(
                self.params, self.pages, jnp.asarray(tokens_pad), np.int32(plen),
                jnp.asarray(ids), key, np.float32(req.temperature),
            )
            carry.generated.append(int(tok))  # admission-time sync, not per-step
            if self._on_stage is not None:
                info = {
                    "rid": req.rid, "plen": plen,
                    "dur_s": time.perf_counter() - pt0,
                }
                if np.isfinite(now) and np.isfinite(req.arrival_time):
                    info["queue_wait_s"] = max(now - req.arrival_time, 0.0)
                self._on_stage("prefill", info)
            carry.token_times.append(now if np.isfinite(now) else 0.0)
            self._slots[slot] = carry
            self._tokens[slot] = carry.generated[-1]
            self._temps[slot] = req.temperature
            if carry.remaining <= 0 or carry.generated[-1] == (
                req.eos_id if req.eos_id is not None else -1
            ):
                self._finish(slot, finished)

    # ------------------------------------------------------------------
    # serving fast path (spec decode / prefix sharing / chunked prefill)
    # ------------------------------------------------------------------
    def _admit_fast(self, now: float, finished: list[RequestOutput]) -> None:
        """Admission with prefix-cache sharing.  With chunked prefill the
        slot joins *cold*: all prompt pages are claimed up front (so chunk
        steps can never stall mid-prompt) but ``seq_len`` starts at the
        shared-prefix length and advances per chunk inside ``_step_fast``.
        Without it, the legacy bucketed prefill runs — shared pages are
        simply dropped from the K/V scatter (copy-on-write: never rewrite
        a page another holder can read)."""
        while True:
            defer_cold = (
                self.prefill_chunk > 0
                and self.prefix is not None
                and any(
                    s is not None
                    and s.cold_prefill
                    and s.prefill_pos < len(self._prompts[i])
                    for i, s in enumerate(self._slots)
                )
            )
            req = self.scheduler.next_admissible(
                self.alloc, self.page_size, now, prefix=self.prefix,
                defer_cold=defer_cold,
            )
            if req is None:
                return
            plen = req.prompt_len
            shared = self.prefix.lookup(req.tokens) if self.prefix else []
            if shared:
                self.counters["prefix_hits"] += 1
                self.counters["pages_shared"] += len(shared)
            carry: _ActiveSeq = getattr(req, "_carry", None) or _ActiveSeq(
                req=req, generated=[], token_times=[]
            )
            carry.queue_wait = (
                max(now - req.arrival_time, 0.0)
                if np.isfinite(now) and np.isfinite(req.arrival_time)
                else -1.0
            )
            slot, page_ids = self.alloc.allocate_slot(
                plen, self.page_size, shared=shared
            )
            self._prompts[slot] = np.asarray(req.tokens, np.int32)
            self._history[slot] = [int(t) for t in req.tokens]
            self._temps[slot] = req.temperature
            if self.prefill_chunk:
                start = len(shared) * self.page_size
                self.alloc.seq_lens[slot] = start
                carry.prefill_pos = start
                carry.prefill_dur = 0.0
                carry.cold_prefill = not shared
                self._slots[slot] = carry
                continue
            # legacy bucketed prefill, minus the rewrite of shared pages
            bucket = _bucket_len(plen, self.page_size, self.max_len)
            tokens_pad = np.zeros((1, bucket), np.int32)
            tokens_pad[0, :plen] = req.tokens
            ids = np.full(
                (bucket // self.page_size,), self.alloc.null_page, np.int32
            )
            ids[: len(page_ids)] = page_ids
            ids[: len(shared)] = self.alloc.null_page
            key = jax.random.fold_in(
                jax.random.fold_in(self._prefill_key, req.rid),
                len(carry.generated),
            )
            pt0 = time.perf_counter()
            self.pages, tok = self._prefill(
                self.params, self.pages, jnp.asarray(tokens_pad), np.int32(plen),
                jnp.asarray(ids), key, np.float32(req.temperature),
            )
            if self.prefix is not None:
                self.prefix.register(req.tokens, page_ids)
            carry.generated.append(int(tok))
            self._history[slot].append(int(tok))
            if self._on_stage is not None:
                info = {
                    "rid": req.rid, "plen": plen,
                    "dur_s": time.perf_counter() - pt0,
                }
                if carry.queue_wait >= 0:
                    info["queue_wait_s"] = carry.queue_wait
                self._on_stage("prefill", info)
            carry.token_times.append(now if np.isfinite(now) else 0.0)
            self._slots[slot] = carry
            self._tokens[slot] = carry.generated[-1]
            if carry.remaining <= 0 or carry.generated[-1] == (
                req.eos_id if req.eos_id is not None else -1
            ):
                self._finish(slot, finished)

    def _extend_or_reclaim(self, slot: int, target_len: int) -> bool:
        """Extend, evicting idle prefix-index pages on a shortfall.  Pages
        in this slot's own table are never reclaimable (a table hold keeps
        their refcount above the index's lone hold)."""
        if self.alloc.extend(slot, target_len, self.page_size):
            return True
        if self.prefix is not None:
            row = self.alloc.block_tables[slot]
            have = int((row != self.alloc.null_page).sum())
            short = (
                pages_for(target_len, self.page_size)
                - have
                - self.alloc.free_page_count
            )
            if short > 0 and self.prefix.reclaim(short) >= short:
                return self.alloc.extend(slot, target_len, self.page_size)
        return False

    def _emit(
        self, slot: int, toks: list[int], t_emit: float,
        finished: list[RequestOutput],
    ) -> None:
        """Append emitted tokens to a slot's record, finishing on EOS or an
        exhausted budget (acceptance never overshoots: drafts are capped at
        ``remaining - 1`` before proposal)."""
        s = self._slots[slot]
        for t in toks:
            s.generated.append(int(t))
            s.token_times.append(t_emit)
            self._history[slot].append(int(t))
            if s.remaining <= 0 or (
                s.req.eos_id is not None and int(t) == s.req.eos_id
            ):
                self._finish(slot, finished)
                return
        self._tokens[slot] = toks[-1]

    def _step_fast(self, now: float) -> list[RequestOutput]:
        """One fast-path engine step: build a per-slot window plan (prefill
        chunk under the step budget, or current token + n-gram drafts), run
        ONE program sized to the widest window class this step needs, then
        verify/accept on the host."""
        finished = self._pending_outputs
        self._admit_fast(now, finished)
        entries: list[tuple[int, str, int, list[int]]] = []
        decodes: list[tuple[int, list[int]]] = []
        stalled: list[int] = []
        budget = self.prefill_chunk
        any_prefill = False
        any_spec = False
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            plen = len(self._prompts[i])
            if self.prefill_chunk and s.prefill_pos < plen:
                c = min(self._q_mixed, plen - s.prefill_pos, budget)
                if c > 0:
                    budget -= c
                    entries.append((i, "prefill", c, []))
                    any_prefill = True
                continue  # pages pre-allocated at admission: never stalls
            drafts: list[int] = []
            if self.spec_k and s.req.temperature == 0 and s.remaining > 1:
                cap = min(self.spec_k, s.remaining - 1)
                drafts = _ngram_propose(
                    np.asarray(self._history[i], np.int32), self.spec_ngram, cap
                )
            decodes.append((i, drafts))
        # Speculation gate: the Q-token program prices the WHOLE batch at
        # window width Q, so drafts only pay when most decode slots ride
        # them.  Unless a prefill chunk already forces the wide program,
        # drop all drafts when the batch averages under spec_k/2 drafted
        # tokens per decode slot; dropped drafts are never counted as
        # proposed (accept-rate = accepted/proposed stays meaningful).
        if decodes and not any_prefill and self.spec_k:
            drafted = sum(len(d) for _, d in decodes)
            if 2 * drafted < len(decodes) * self.spec_k:
                decodes = [(i, []) for i, _ in decodes]
        for i, drafts in decodes:
            target = int(self.alloc.seq_lens[i]) + 1 + len(drafts)
            ok = self._extend_or_reclaim(i, target)
            if not ok and drafts:
                drafts = []
                ok = self._extend_or_reclaim(
                    i, int(self.alloc.seq_lens[i]) + 1
                )
            if not ok:
                stalled.append(i)
                continue
            self.counters["spec_proposed"] += len(drafts)
            if drafts:
                any_spec = True
            entries.append((i, "decode", 1 + len(drafts), drafts))
        if not entries:
            if stalled:
                self._preempt_one(stalled)
            self._pending_outputs = []
            return finished
        Q = (
            self._q_mixed if any_prefill
            else (self._q_decode if any_spec else 1)
        )
        tokens_mat = np.zeros((self.num_slots, Q), np.int32)
        sample_idx = np.zeros((self.num_slots,), np.int32)
        step_tokens = 0
        for i, kind, qlen, drafts in entries:
            if kind == "prefill":
                pos = self._slots[i].prefill_pos
                tokens_mat[i, :qlen] = self._prompts[i][pos:pos + qlen]
            else:
                tokens_mat[i, 0] = self._tokens[i]
                if drafts:
                    tokens_mat[i, 1:1 + len(drafts)] = drafts
            sample_idx[i] = qlen - 1 if kind == "prefill" else 0
            step_tokens += qlen
        dt0 = time.perf_counter()
        if Q == 1:
            active = np.zeros((self.num_slots,), bool)
            for i, _, _, _ in entries:
                active[i] = True
            tok_dev, self.pages = self._decode(
                self.params, self.pages, jnp.asarray(self._tokens),
                jnp.asarray(self.alloc.block_tables),
                jnp.asarray(self.alloc.seq_lens), jnp.asarray(active),
                jnp.asarray(self._temps), self._decode_key,
                np.int32(self._counter),
            )
            toks = np.asarray(tok_dev)
            greedy = toks[:, None]
            sampled = toks
        else:
            greedy_dev, sampled_dev, self.pages = self._multi(
                self.params, self.pages, jnp.asarray(tokens_mat),
                jnp.asarray(self.alloc.block_tables),
                jnp.asarray(self.alloc.seq_lens), jnp.asarray(self._temps),
                jnp.asarray(sample_idx), self._decode_key,
                np.int32(self._counter),
            )
            greedy = np.asarray(greedy_dev)  # the scheduler's sync point
            sampled = np.asarray(sampled_dev)
        self._counter += 1
        step_dur = time.perf_counter() - dt0
        t_emit = now if np.isfinite(now) else 0.0
        emitted = 0
        for i, kind, qlen, drafts in entries:
            s = self._slots[i]
            if kind == "prefill":
                s.prefill_pos += qlen
                self.alloc.seq_lens[i] = s.prefill_pos
                self.counters["prefill_chunks"] += 1
                s.prefill_dur += step_dur * (qlen / max(step_tokens, 1))
                plen = len(self._prompts[i])
                if s.prefill_pos < plen:
                    continue
                # prompt complete: register its full pages, report the
                # prefill stage, and emit the first sampled token
                if self.prefix is not None:
                    n_full = (plen - 1) // self.page_size
                    row = self.alloc.block_tables[i]
                    self.prefix.register(
                        self._prompts[i], [int(p) for p in row[:n_full]]
                    )
                if self._on_stage is not None:
                    info = {
                        "rid": s.req.rid, "plen": plen, "dur_s": s.prefill_dur,
                    }
                    if s.queue_wait >= 0:
                        info["queue_wait_s"] = s.queue_wait
                    self._on_stage("prefill", info)
                emitted += 1
                self._emit(i, [int(sampled[i])], t_emit, finished)
                continue
            if s.req.temperature > 0:
                emit = [int(sampled[i])]
            else:
                g = greedy[i]
                emit = [int(g[0])]
                for j, d in enumerate(drafts):
                    if int(d) != int(g[j]):
                        break
                    emit.append(int(g[j + 1]))
                self.counters["spec_accepted"] += len(emit) - 1
            self.alloc.seq_lens[i] += len(emit)
            emitted += len(emit)
            self._emit(i, emit, t_emit, finished)
        if self._on_stage is not None:
            self._on_stage("decode", {
                "dur_s": step_dur,
                "slots": len(entries),
                "tokens": emitted,
            })
        self._pending_outputs = []
        return finished

    def _continuation(self, slot: int) -> Request:
        """Evict ``slot`` into a continuation request: the full prefix
        (prompt + generated so far) re-prefills on readmission, and the
        carried host record keeps accumulating into the same output.  The
        readmission prefill key folds in the generated count, so its sampling
        stream does not repeat the first admission's."""
        s = self._slots[slot]
        cont = Request(
            rid=s.req.rid,
            tokens=np.concatenate(
                [s.req.tokens, np.asarray(s.generated, np.int32)]
            ),
            max_new_tokens=s.req.max_new_tokens,
            temperature=s.req.temperature,
            arrival_time=0.0,
            eos_id=s.req.eos_id,
            deadline_s=s.req.deadline_s,
        )
        cont._carry = s  # type: ignore[attr-defined]
        self.alloc.release(slot)
        self._slots[slot] = None
        self._temps[slot] = 0.0
        return cont

    def _preempt_one(self, stalled: list[int]) -> None:
        """Pool exhausted and nothing can advance: evict the youngest stalled
        sequence and requeue it as a continuation."""
        victim = min(stalled, key=lambda i: int(self.alloc.seq_lens[i]))
        self.scheduler.pending.appendleft(self._continuation(victim))

    def drain_continuations(self) -> list[Request]:
        """Evict every in-flight sequence and drain the queue as resumable
        requests — the hand-off hook the replica router (replica failure) and
        the platform's preempt-mid-run path use to move work off this engine.
        The engine is left idle with all pages free."""
        conts = [
            self._continuation(i)
            for i, s in enumerate(self._slots)
            if s is not None
        ]
        conts.extend(self.scheduler.pending)
        self.scheduler.pending.clear()
        return conts

    def cancel(self, rid: int) -> bool:
        """Drop every trace of request ``rid`` — queued copies (including
        requeued continuations) and its decode slot — without emitting an
        output.  The hedged-dispatch loser path: the winning cell already
        delivered this rid, so the work is abandoned, not salvaged.
        Returns whether anything was removed."""
        hit = False
        if any(r.rid == rid for r in self.scheduler.pending):
            self.scheduler.pending = deque(
                r for r in self.scheduler.pending if r.rid != rid
            )
            hit = True
        for i, s in enumerate(self._slots):
            if s is not None and s.req.rid == rid:
                self.alloc.release(i)
                self._slots[i] = None
                self._temps[i] = 0.0
                hit = True
        return hit

    def load_tokens(self) -> int:
        """Live tokens in decode slots plus queued prompt tokens — the
        join-shortest-queue admission signal the replica router balances on."""
        return self.alloc.live_tokens() + sum(
            r.prompt_len for r in self.scheduler.pending
        )

    def queue_depth(self) -> int:
        """Requests waiting for a slot — the sustained-pressure signal
        replica/cell autoscaling watches."""
        return len(self.scheduler.pending)

    # ------------------------------------------------------------------
    # serving loop
    # ------------------------------------------------------------------
    def step(self, now: float = float("inf")) -> list[RequestOutput]:
        """Admit arrivals, advance every active slot one token, evict the
        finished.  Returns requests completed during this step."""
        if self.fastpath:
            return self._step_fast(now)
        # accumulate into the instance buffer: if decode raises mid-step,
        # admission-time completions are retained for drain_finished()
        finished = self._pending_outputs
        self._admit(now, finished)
        active = np.array([s is not None for s in self._slots])
        if not active.any():
            self._pending_outputs = []
            return finished
        stalled = []
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            if not self.alloc.extend(
                i, int(self.alloc.seq_lens[i]) + 1, self.page_size
            ):
                active[i] = False
                stalled.append(i)
        if not active.any():
            self._preempt_one(stalled)
            self._pending_outputs = []
            return finished
        dt0 = time.perf_counter()
        tok_dev, self.pages = self._decode(
            self.params,
            self.pages,
            jnp.asarray(self._tokens),
            jnp.asarray(self.alloc.block_tables),
            jnp.asarray(self.alloc.seq_lens),
            jnp.asarray(active),
            jnp.asarray(self._temps),
            self._decode_key,
            np.int32(self._counter),
        )
        self._counter += 1
        toks = np.asarray(tok_dev)  # the scheduler's sync point
        if self._on_stage is not None:
            self._on_stage("decode", {
                "dur_s": time.perf_counter() - dt0,
                "slots": int(active.sum()),
            })
        t_emit = now if np.isfinite(now) else 0.0
        for i in np.flatnonzero(active):
            s = self._slots[i]
            self.alloc.seq_lens[i] += 1
            s.generated.append(int(toks[i]))
            s.token_times.append(t_emit)
            self._tokens[i] = toks[i]
            done = s.remaining <= 0 or (
                s.req.eos_id is not None and s.generated[-1] == s.req.eos_id
            )
            if done:
                self._finish(int(i), finished)
        self._pending_outputs = []
        return finished

    def drain_finished(self) -> list[RequestOutput]:
        """Outputs completed by a step() that raised before returning —
        the router collects these when failing a replica over."""
        finished, self._pending_outputs = self._pending_outputs, []
        return finished

    def has_work(self) -> bool:
        return bool(len(self.scheduler)) or any(
            s is not None for s in self._slots
        )

    def next_arrival(self) -> Optional[float]:
        """Queue head's arrival time when the engine is fully idle; None if
        it can make progress right now.  Lets a caller (engine.run, or the
        replica router) sleep out a trace gap instead of busy-spinning."""
        if any(s is not None for s in self._slots):
            return None
        if not self.scheduler.pending:
            return None
        return self.scheduler.pending[0].arrival_time

    def run(self, requests: Optional[list[Request]] = None) -> list[RequestOutput]:
        """Serve a trace to completion; ``arrival_time`` is honoured against
        a wall clock starting at the first call."""
        for r in requests or []:
            self.submit(r)
        outs: list[RequestOutput] = []
        t0 = time.perf_counter()
        while self.has_work():
            now = time.perf_counter() - t0
            pending = self.scheduler.pending
            if not any(s is not None for s in self._slots) and pending:
                wait = pending[0].arrival_time - now
                if wait > 0:
                    time.sleep(wait)
                    now = time.perf_counter() - t0
                elif not charged_can_admit(
                    self.alloc, pending[0].tokens, self.page_size, self.prefix
                ):
                    # nothing active, head has arrived and still can't fit:
                    # no step can change that — fail loudly, don't busy-spin
                    raise RuntimeError(
                        f"request {pending[0].rid} is unadmissible with all "
                        f"slots idle ({pending[0].prompt_len + 1} tokens vs "
                        f"{self.alloc.free_page_count} free pages)"
                    )
            outs.extend(self.step(now))
        return outs
