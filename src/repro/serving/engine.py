"""Inference engine: jitted prefill / decode steps + generation loops.

The engine is what the LLMBridge model pool calls into for every model.
Two decode drivers:

* ``generate``      — Python loop over a jitted single-token step (the real
                      serving path; composes with the continuous-batching
                      scheduler which mutates slots between steps);
* ``generate_scan`` — fully jitted ``lax.scan`` decode (benchmarks; no
                      per-step host round-trip).

``serve_step`` is the artifact the multi-pod dry-run lowers for the decode
shapes: ONE new token against a (seq_len)-deep KV cache.

``DraftEngine`` wraps a SMALL family sibling for speculative decoding on
the paged scheduler: it drafts k greedy tokens per slot against its own
dense KV cache; the big model then verifies all k+1 positions in ONE
decode-shaped paged step and keeps the longest agreeing prefix
(scheduler._spec_step).  ``OracleDraftEngine`` is the benchmark variant:
it pays the same draft compute but proposes from a known continuation with
a controlled per-token acceptance probability.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.models import apply_model, init_cache, init_paged_cache, vlm
from repro.models.config import ModelConfig
from repro.serving.sampler import SamplerConfig, sample

# ``generate`` polls the device-side done mask only every N steps: the
# ``bool(done.all())`` early-exit forces a host round-trip per token, which
# stalls the dispatch pipeline far longer than the handful of speculative
# decode steps the coarser poll may run past the last EOS.
DONE_POLL_EVERY = 8


class Engine:
    def __init__(self, cfg: ModelConfig, params: Dict, max_len: int = 256):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        # named after the step functions, so a device trace's programs
        # read ``jit_prefill_step`` and ``jit_decode_step``
        self._prefill = jax.jit(_bind_cfg(prefill_step, cfg))
        self._decode = jax.jit(_bind_cfg(decode_step, cfg))
        # telemetry: the batched admission path must collapse a refill's
        # prefills into one call per group, and the decode loop must not
        # sync the done mask per token (benchmarks/serving_latency.py)
        self.n_prefill_calls = 0
        self.n_prefill_tokens = 0
        self.n_host_syncs = 0

    def prefill(self, tokens: jax.Array, cache: Dict, **extras):
        self.n_prefill_calls += 1
        self.n_prefill_tokens += int(tokens.shape[0]) * int(tokens.shape[1])
        with telemetry.span("engine.prefill"):
            return self._prefill(self.params, tokens, cache, **extras)

    def new_cache(self, batch: int, max_len: Optional[int] = None) -> Dict:
        return init_cache(self.cfg, batch, max_len or self.max_len)

    def new_paged_cache(self, batch: int, n_pages: int, page_size: int,
                        max_pages: int) -> Dict:
        """Global page pool + per-slot page tables (attention-only families);
        allocator/trie metadata lives with the scheduler
        (serving/kv_cache.PagePool)."""
        return init_paged_cache(self.cfg, batch, n_pages, page_size, max_pages)

    def decode(self, tokens: jax.Array, positions: jax.Array, cache: Dict):
        with telemetry.span("engine.decode"):
            return self._decode(self.params, tokens, positions, cache)

    def generate(self, prompt: jax.Array, max_new: int,
                 sampler: SamplerConfig = SamplerConfig(),
                 key: Optional[jax.Array] = None,
                 eos_id: int = -1) -> jax.Array:
        """prompt: (B, S). Returns (B, max_new) generated ids."""
        key = key if key is not None else jax.random.PRNGKey(0)
        B, S = prompt.shape
        extras = {}
        n_prefix = 0
        if self.cfg.family == "vlm":
            extras["img_embeds"] = vlm.patch_embeddings(self.cfg, B)
            n_prefix = vlm.n_patches(self.cfg)
        if self.cfg.family == "audio":
            extras["frames"] = jnp.zeros((B, self.cfg.n_frames, self.cfg.d_encoder),
                                         self.cfg.dtype)
        cache = self.new_cache(B, max(self.max_len, S + n_prefix + max_new + 1))
        logits, cache = self.prefill(prompt, cache, **extras)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        out = []
        pos = S + n_prefix
        done = jnp.zeros((B,), bool)
        all_done_hist = []        # device-side per-step all-done flags
        for i in range(max_new):
            out.append(tok)
            key, sub = jax.random.split(key)
            positions = jnp.full((B, 1), pos + i, jnp.int32)
            logits, cache = self.decode(tok[:, None], positions, cache)
            tok = sample(logits[:, -1], sub, sampler)
            # early-exit bookkeeping stays on device; the host polls only
            # every DONE_POLL_EVERY steps, and only when EOS can fire at all
            # (eos_id < 0 can never complete early — zero syncs)
            if eos_id >= 0:
                done = done | (tok == eos_id)
                all_done_hist.append(done.all())
                if (i + 1) % DONE_POLL_EVERY == 0 and self._poll_done(done):
                    break
        if all_done_hist:
            # trim the speculative tail: tokens after the step at which every
            # row had emitted EOS match the per-step-sync loop bit-for-bit,
            # because sampling keys are split per step regardless of polling
            hist = np.asarray(jnp.stack(all_done_hist))
            first = int(np.argmax(hist)) if hist.any() else len(out) - 1
            out = out[:first + 1]
        return jnp.stack(out, axis=1)

    def _poll_done(self, done: jax.Array) -> bool:
        self.n_host_syncs += 1
        return bool(done.all())

    def generate_stream(self, prompt: jax.Array, max_new: int,
                        sampler: SamplerConfig = SamplerConfig(),
                        key: Optional[jax.Array] = None,
                        eos_id: int = -1):
        """Step-wise generator twin of ``generate``: yields one (B,) int32
        host array per decode step — the streaming front door's per-token
        path.  Stacking the yields along axis=1 reproduces ``generate``'s
        output bit-for-bit (same prefill argmax, same per-step key splits,
        same EOS trim: the step at which every row has emitted EOS is the
        last one yielded).  The per-token host transfer ``generate`` batches
        away is inherent here — the consumer needs each token on the host
        to forward it downstream.
        """
        key = key if key is not None else jax.random.PRNGKey(0)
        B, S = prompt.shape
        extras = {}
        n_prefix = 0
        if self.cfg.family == "vlm":
            extras["img_embeds"] = vlm.patch_embeddings(self.cfg, B)
            n_prefix = vlm.n_patches(self.cfg)
        if self.cfg.family == "audio":
            extras["frames"] = jnp.zeros((B, self.cfg.n_frames, self.cfg.d_encoder),
                                         self.cfg.dtype)
        cache = self.new_cache(B, max(self.max_len, S + n_prefix + max_new + 1))
        logits, cache = self.prefill(prompt, cache, **extras)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        pos = S + n_prefix
        done = jnp.zeros((B,), bool)
        for i in range(max_new):
            yield np.asarray(tok)
            key, sub = jax.random.split(key)
            positions = jnp.full((B, 1), pos + i, jnp.int32)
            logits, cache = self.decode(tok[:, None], positions, cache)
            tok = sample(logits[:, -1], sub, sampler)
            if eos_id >= 0:
                done = done | (tok == eos_id)
                if self._poll_done(done):
                    return


def prefill_step(params: Dict, tokens: jax.Array, cache: Dict, *,
                 cfg: ModelConfig, img_embeds=None, frames=None
                 ) -> Tuple[jax.Array, Dict]:
    B, S = tokens.shape
    n_prefix = vlm.n_patches(cfg) if (cfg.family == "vlm" and img_embeds is not None) else 0
    positions = jnp.broadcast_to(
        jnp.arange(S + n_prefix, dtype=jnp.int32)[None], (B, S + n_prefix))
    if cfg.family != "vlm":
        positions = positions[:, :S]
    logits, new_cache, _ = apply_model(
        params, tokens, cfg, positions=positions, cache=cache,
        img_embeds=img_embeds, frames=frames)
    if cfg.family == "vlm" and img_embeds is not None:
        logits = logits[:, n_prefix:]
    return logits, new_cache


def decode_step(params: Dict, tokens: jax.Array, positions: jax.Array,
                cache: Dict, *, cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """tokens: (B, S); positions: (B, S) absolute positions.

    S == 1 is the plain decode step; S > 1 is a decode-shaped block — a
    suffix prefill against resident pages, a speculative verify window, or
    a draft catch-up — writing KV at each slot's cursor and attending with
    per-row causal masking."""
    logits, new_cache, _ = apply_model(params, tokens, cfg,
                                       positions=positions, cache=cache)
    return logits, new_cache


def serve_step(params: Dict, tokens: jax.Array, positions: jax.Array,
               cache: Dict, *, cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """Dry-run artifact for decode shapes: one token, deep KV cache."""
    return decode_step(params, tokens, positions, cache, cfg=cfg)


def _bind_cfg(step, cfg: ModelConfig):
    """``step`` with its config bound, keeping the step's name."""
    bound = functools.partial(step, cfg=cfg)
    bound.__name__ = bound.__qualname__ = step.__name__
    return bound


def _pow2(n: int, lo: int = 1) -> int:
    return max(lo, 1 << (max(n, 1) - 1).bit_length())


class DraftEngine:
    """Greedy draft proposer for speculative decoding (one per Scheduler).

    Wraps an :class:`Engine` holding the SMALL family sibling and keeps a
    dense KV cache with one row per scheduler slot.  Each round,
    ``propose`` (a) *catches up* — feeds every live slot's not-yet-fed
    history tokens in one right-padded block at their absolute positions —
    then (b) single-steps ``k-1`` times to emit k greedy proposals per
    slot.  Validity of the draft cache is tracked host-side as a per-slot
    fed-prefix length ``dpos``: after the big model verifies, ``commit``
    rewinds it to the longest prefix whose KV matches the accepted history
    (accepted proposals were fed, so their KV is reusable — catch-up width
    stays 1, or 2 on a full-window accept), and rejected draft KV is simply
    left above the cursor where the write-then-attend discipline overwrites
    it before it can ever be read.  The cache carries ``HEADROOM`` rows of
    depth beyond ``max_len`` so right-padding can never clamp-smear onto a
    live position.
    """

    HEADROOM = 16

    def __init__(self, engine: Engine, n_slots: int, max_len: int):
        cache = engine.new_cache(n_slots, max_len + self.HEADROOM)
        if "kv" not in cache:
            raise ValueError(
                f"draft model {engine.cfg.name!r} ({engine.cfg.family}) has "
                "no dense KV cursor; speculative drafting needs an "
                "attention-family draft")
        self.engine = engine
        self.n_slots = n_slots
        self.max_len = max_len
        self.cache = cache
        self.dpos = np.zeros(n_slots, np.int64)   # tokens already fed, per slot
        self._hist_at_propose = np.zeros(n_slots, np.int64)
        self.draft_time = 0.0                     # propose() wall time (s)
        self.trust_cache = True

    def reset(self, slots) -> None:
        """Forget a slot's history (teardown / re-admission); stale KV above
        the cursor is dead by the write-then-attend discipline."""
        for slot in slots:
            self.dpos[slot] = 0

    def _run(self, toks_np: np.ndarray, base: np.ndarray,
             widths: np.ndarray) -> jax.Array:
        """Feed ``toks_np[b, :widths[b]]`` at positions ``base[b]..`` for
        every slot in one call; pad rows write junk above each cursor (dead)
        and their logits are ignored.  Returns logits (B, W, V)."""
        Ln = self.cache["kv"]["pos"].shape[0]
        B, W = toks_np.shape
        base_dev = jnp.asarray(base, jnp.int32)
        self.cache["kv"]["pos"] = jnp.broadcast_to(base_dev[None], (Ln, B))
        positions = base_dev[:, None] + jnp.arange(W, dtype=jnp.int32)[None]
        logits, self.cache = self.engine.decode(
            jnp.asarray(toks_np, jnp.int32), positions, self.cache)
        # per-slot cursor rewind: the uniform-width call advanced every row
        # by W; the true fed-prefix grew by each slot's REAL width
        self.dpos = base + widths
        return logits

    def propose(self, items, k: int) -> np.ndarray:
        """items: list of (slot, req, hist) for live slots, ``hist`` the full
        host token history (prompt + generated).  Returns proposals
        (n_slots, k) int32 — rows of slots not in ``items`` are garbage.
        """
        t0 = time.monotonic()
        slots = [s for s, _, _ in items]
        base = self.dpos.copy()
        widths = np.zeros(self.n_slots, np.int64)
        hlen = np.zeros(self.n_slots, np.int64)
        for slot, _req, hist in items:
            hlen[slot] = len(hist)
            base[slot] = min(self.dpos[slot], len(hist) - 1)
            widths[slot] = len(hist) - base[slot]
        W = _pow2(int(widths.max()))
        toks = np.zeros((self.n_slots, W), np.int32)
        for slot, _req, hist in items:
            toks[slot, :widths[slot]] = hist[int(base[slot]):]
        logits = self._run(toks, base, widths)
        rows = jnp.asarray(np.maximum(widths - 1, 0), jnp.int32)
        cur = jnp.argmax(
            logits[jnp.arange(self.n_slots), rows], -1).astype(jnp.int32)
        props = [cur]
        live = widths > 0
        for i in range(1, k):
            step_base = np.where(live, hlen + i - 1, self.dpos)
            logits = self._run(np.asarray(cur)[:, None].astype(np.int32),
                               step_base, live.astype(np.int64))
            cur = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
            props.append(cur)
        self._hist_at_propose[slots] = hlen[slots]
        out = np.asarray(jnp.stack(props, axis=1))            # (n_slots, k)
        self.draft_time += time.monotonic() - t0
        return out

    def commit(self, slot: int, accepted: int, k: int, new_hlen: int) -> None:
        """After verification: ``accepted`` of the k proposals matched and
        the big model's history is now ``new_hlen`` tokens.  Proposals
        1..k-1 were fed (proposal k never is), so their KV is trusted up to
        the accepted prefix; an oracle draft's cache never is (its fed
        tokens differ from its reported proposals)."""
        h = int(self._hist_at_propose[slot])
        valid = h + min(accepted, k - 1) if self.trust_cache else h
        self.dpos[slot] = min(valid, new_hlen - 1)


class OracleDraftEngine(DraftEngine):
    """Benchmark draft with a CONTROLLED acceptance rate.

    Runs the real draft machinery (same compute, same wall time) but
    replaces each slot's proposals using a known greedy continuation
    (rid -> token list, recorded from a non-speculative baseline run):
    every position independently proposes the true next token with
    probability ``accept_p``, and a guaranteed-wrong token otherwise, so
    measured speedups correspond to a chosen acceptance rate instead of
    whatever a tiny random-weight draft happens to produce.  The cache is
    never trusted — fed tokens diverge from reported proposals — so
    catch-up re-feeds the accepted window each round.
    """

    def __init__(self, engine: Engine, n_slots: int, max_len: int,
                 continuations: Dict[int, list], accept_p: float,
                 seed: int = 0):
        super().__init__(engine, n_slots, max_len)
        self.continuations = continuations
        self.accept_p = accept_p
        self.rng = np.random.default_rng(seed)
        self.trust_cache = False

    def propose(self, items, k: int) -> np.ndarray:
        props = np.array(super().propose(items, k))   # writable copy
        vocab = self.engine.cfg.vocab
        for slot, req, hist in items:
            cont = self.continuations.get(req.rid, [])
            done = len(req.generated)           # next true token index
            for j in range(k):
                idx = done + j
                truth = cont[idx] if idx < len(cont) else 0
                if self.rng.random() < self.accept_p:
                    props[slot, j] = truth
                else:
                    props[slot, j] = (truth + 1) % vocab
        return props


def generate_scan(params: Dict, cfg: ModelConfig, prompt: jax.Array,
                  max_new: int, cache: Dict) -> jax.Array:
    """Fully jitted greedy decode (benchmark path)."""
    B, S = prompt.shape
    logits, cache = prefill_step(params, prompt, cache, cfg=cfg)
    tok0 = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)

    def body(carry, i):
        tok, cache = carry
        positions = jnp.full((B, 1), S, jnp.int32) + i
        logits, cache = decode_step(params, tok[:, None], positions, cache, cfg=cfg)
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        return (nxt, cache), tok

    (_, _), toks = jax.lax.scan(body, (tok0, cache), jnp.arange(max_new))
    return jnp.moveaxis(toks, 0, 1)
