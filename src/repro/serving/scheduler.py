"""Continuous-batching scheduler with per-user FIFO queues + paged KV admission.

The paper's deployment funnels every WhatsApp request through a per-user
FIFO (AWS SQS) so responses arrive in order (§4).  This scheduler reproduces
that discipline inside the serving engine:

* one in-flight request per user at a time; later requests wait in that
  user's queue;
* a fixed pool of decode slots (the continuous batch); freed slots are
  refilled from user queues round-robin;
* admission = prefill + cache insertion, with two cache backends:

  - **dense** (default): one (n_slots, max_len) KV region per slot; a refill
    is ONE right-padded prefill + ONE ``kv_cache.insert_slots``; finished
    slots are torn down in ONE ``kv_cache.reset_slots`` pass per step;
  - **paged** (``paged=True``, attention-only families): fixed-size pages in
    one global HBM tensor, per-slot page tables, and a refcounted
    :class:`~repro.serving.kv_cache.PagePool` with copy-on-write prefix
    sharing.  ``_admit`` consults a token-hash :class:`PrefixTrie`: prompts
    whose leading pages are already prefilled (classroom workloads — shared
    course prompts, assignment scaffolds) skip their prefill entirely and
    decode against the SAME physical pages; only the unmatched suffix runs
    through the model.  Admission is **page-budgeted** (reserve pages, not
    slots: short requests stop pinning ``max_len`` of HBM), decode pages are
    allocated lazily the step a slot's cursor crosses a page boundary, and
    cold prefix pages are LRU-evicted under pressure.

On the paged backend the decode loop can run **speculatively** (pass a
:class:`~repro.serving.engine.DraftEngine`): each round the small family
sibling drafts ``spec_k`` greedy tokens per slot, the big model verifies
all k+1 positions in ONE decode-shaped step against the paged KV, and the
longest agreeing prefix (plus the verifier's own next token) is emitted.
Every emitted token is the VERIFIER's argmax, so output is bit-exact with
plain greedy decode — acceptance only sets the speed.  Rejected draft KV is
rolled back by rewinding the page cursors (`pos`); the scatter-then-attend
discipline overwrites it before any query can see it, so no pages need
releasing and copy-on-write sharing is untouched.  A pair that fails the
compatibility gate (``configs.spec_decode_compatible``, greedy sampling,
matching slot counts) degrades to plain decode with the reason recorded in
``spec_stats`` — never to wrong tokens.

This is the substrate under LLMBridge's model pool: every pool model gets an
Engine + Scheduler pair.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import telemetry
from repro.serving import discipline, kv_cache
from repro.serving.engine import DraftEngine, Engine
from repro.serving.sampler import SamplerConfig, sample


@dataclasses.dataclass
class Request:
    rid: int
    user: str
    prompt: jax.Array              # (S,) int32
    max_new: int = 32
    eos_id: int = -1
    # latency budget in seconds from submission (None = best-effort).
    # Admission serves tight-budget requests earliest-deadline-first against
    # the arrival-adjusted deadline ``submitted_at + deadline`` (LLMBridge
    # threads ``Constraints.max_latency`` through ``request_batch`` to here).
    deadline: Optional[float] = None
    # BudgetLedger depletion tier (0 = fully funded).  Slot refill weighs it
    # alongside EDF: depleted traffic yields decode slots under contention,
    # until the starvation guard ages the request back to full priority.
    tier: int = 0
    # filled during serving
    submitted_at: float = 0.0
    generated: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    pos: int = 0
    done: bool = False


def _pow2_bucket(n: int, lo: int = 16) -> int:
    """Pad a length to a power of two (>= lo) so the jit compile set stays
    logarithmic in the length range instead of one program per length."""
    return max(lo, 1 << (max(n, 1) - 1).bit_length())


class Scheduler:
    def __init__(self, engine: Engine, n_slots: int = 8,
                 sampler: SamplerConfig = SamplerConfig(),
                 max_len: Optional[int] = None, seed: int = 0,
                 tier_penalty: float = 0.25, starvation_s: float = 2.0,
                 paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None, prefix_cache: bool = True,
                 draft: Optional[DraftEngine] = None, spec_k: int = 4):
        self.engine = engine
        self.n_slots = n_slots
        self.sampler = sampler
        # budget-aware refill: each depletion tier costs ``tier_penalty``
        # seconds of effective deadline slack; a head that has waited
        # ``starvation_s`` regains full priority (bounded wait, no starvation)
        self.tier_penalty = tier_penalty
        self.starvation_s = starvation_s
        self.max_len = max_len or engine.max_len
        self.queues: Dict[str, collections.deque] = collections.defaultdict(collections.deque)
        self.user_inflight: Dict[str, bool] = collections.defaultdict(bool)
        self.slots: List[Optional[Request]] = [None] * n_slots
        self.paged = paged
        if paged:
            # page-budgeted HBM: n_pages * page_size cache tokens total; the
            # default matches the dense footprint (n_slots * max_len) + the
            # pinned trash page, so paged-vs-dense sweeps compare equal HBM
            self.page_size = page_size
            self.max_pages = -(-self.max_len // page_size)
            self.n_pages = n_pages or (n_slots * self.max_pages + 1)
            self.trie = kv_cache.PrefixTrie(page_size) if prefix_cache else None
            self.pool = kv_cache.PagePool(self.n_pages, page_size,
                                          trie=self.trie, sentinel=True)
            self.cache = engine.new_paged_cache(n_slots, self.n_pages,
                                               page_size, self.max_pages)
            if set(self.cache["paged"].keys()) != {"k_pages", "v_pages",
                                                   "table", "pos"}:
                raise ValueError("paged scheduling needs a paged KV cache")
            self._tables = np.full((n_slots, self.max_pages), -1, np.int32)
            self._slot_unreserved = np.zeros(n_slots, np.int64)
            self._pad_ok = True
            self._host_prompt: Dict[int, List[int]] = {}
        else:
            self.cache = engine.new_cache(n_slots, self.max_len)
            # attention-only caches admit mixed-length groups via right-padding
            # (pad KV is dead under the causal mask once the cursor is rewound);
            # recurrent caches have no cursor and batch equal lengths only
            self._pad_ok = set(self.cache.keys()) <= {"kv"}
        self.tokens = jnp.zeros((n_slots,), jnp.int32)
        self.key = jax.random.PRNGKey(seed)
        self.finished: List[Request] = []
        self._rr_start = 0                # round-robin start index over users
        self._users_order: List[str] = []
        # telemetry for the paged-vs-dense sweep (benchmarks/serving_latency)
        self.prefill_tokens = 0           # real (unpadded) tokens prefilled
        self.shared_tokens = 0            # prompt tokens served from the trie
        self.peak_live = 0                # max concurrently admitted slots
        telemetry.count("sched.built")
        # -- speculative decoding (draft-model propose, big-model verify) ----
        # a draft only engages when every correctness precondition holds;
        # anything else degrades to plain decode with the reason on record
        # (never to wrong tokens)
        self.draft: Optional[DraftEngine] = None
        self.spec_k = spec_k
        self.spec_stats = {"rounds": 0, "proposed": 0, "accepted": 0,
                           "emitted": 0, "draft_time": 0.0, "verify_time": 0.0,
                           "enabled": False, "disabled_reason": None}
        if draft is not None:
            reason = None
            if not paged:
                reason = "speculative decoding requires the paged cache"
            elif sampler.temperature > 0:
                reason = "speculative decoding is greedy-only"
            elif draft.n_slots != n_slots:
                reason = (f"draft engine has {draft.n_slots} slots, "
                          f"scheduler has {n_slots}")
            elif not configs.spec_decode_compatible(engine.cfg,
                                                    draft.engine.cfg):
                reason = (f"draft {draft.engine.cfg.name!r} is not token-"
                          f"compatible with {engine.cfg.name!r}")
            elif spec_k < 1:
                reason = f"spec_k={spec_k} proposes nothing"
            if reason is None:
                self.draft = draft
                self.spec_stats["enabled"] = True
            else:
                self.spec_stats["disabled_reason"] = reason

    # -- submission ----------------------------------------------------------
    def submit(self, req: Request) -> None:
        if self.paged and int(req.prompt.shape[0]) + 1 > self.max_len:
            # reject up front — a raise mid-admission would strand the popped
            # request and leave its user permanently marked in-flight
            raise ValueError(
                f"request {req.rid}: prompt of {int(req.prompt.shape[0])} "
                f"tokens cannot decode within max_len={self.max_len}")
        req.submitted_at = time.monotonic()
        if req.user not in self.queues:
            self._users_order.append(req.user)
        self.queues[req.user].append(req)

    def pending(self) -> int:
        return sum(len(q) for q in self.queues.values()) + \
            sum(1 for s in self.slots if s is not None)

    # -- admission -----------------------------------------------------------
    def _next_request(self) -> Optional[Request]:
        """Round-robin over users; respect one-in-flight-per-user FIFO.

        The scan start rotates past the last admitted user so users early in
        ``_users_order`` cannot starve later ones when slots are scarce.
        Among eligible users, heads carrying a latency ``deadline`` are
        admitted earliest-deadline-first (they paid for a latency budget);
        deadline-free traffic keeps the plain rotation.  Both orders weigh
        the head's BudgetLedger ``tier``: each depletion level adds
        ``tier_penalty`` seconds of effective deadline slack (deadlined) or
        demotes the head behind funded users (deadline-free) — but a head
        that has waited ``starvation_s`` ages back to tier 0, so depleted
        traffic is deferred, never starved."""
        users = self._users_order
        eligible = []          # (rotation offset, user)
        for i in range(len(users)):
            user = users[(self._rr_start + i) % len(users)]
            if self.queues[user] and not self.user_inflight[user]:
                eligible.append((i, user))
        if not eligible:
            return None
        now = time.monotonic()

        def deadline_of(user):
            head = self.queues[user][0]
            if head.deadline is None:
                return None
            # arrival-adjusted EDF: urgency grows as a request waits
            return head.submitted_at + head.deadline

        def tier_of(user):
            head = self.queues[user][0]
            if now - head.submitted_at >= self.starvation_s:
                return 0       # aged past the guard: full priority again
            return head.tier

        i, user = discipline.select_rotating_head(
            eligible, deadline_of, tier_of, self.tier_penalty)
        self.user_inflight[user] = True
        self._rr_start = (self._rr_start + i + 1) % len(users)
        return self.queues[user].popleft()

    def _put_back(self, req: Request) -> None:
        """Return an un-admittable head to the front of its queue (page
        budget exhausted); it stays next in line without losing FIFO order."""
        self.queues[req.user].appendleft(req)
        self.user_inflight[req.user] = False

    def _admit(self) -> None:
        if self.paged:
            self._admit_paged()
        else:
            self._admit_dense()

    def _admit_dense(self) -> None:
        """Refill free decode slots with ONE prefill + ONE ``insert_slots``
        per admitted group (not per request).

        Mixed-length prompts are right-padded to the group max: with causal
        attention the pad tokens only write KV *after* every real token, and
        each slot's write cursor is rewound to its real length, so decode
        overwrites the pad KV before it ever becomes attendable — bit-exact
        with per-request prefill.  Recurrent caches (SSM/xLSTM hybrids) have
        no such cursor, so for them only equal-length groups are batched and
        lengths fall back to per-group calls.
        """
        free = [i for i, s in enumerate(self.slots) if s is None]
        admitted: List[Request] = []
        for _ in free:
            req = self._next_request()
            if req is None:
                break
            admitted.append(req)
        if not admitted:
            return
        pairs = list(zip(free, admitted))
        if self._pad_ok:
            groups = [pairs]                       # attention-only: pad freely
        else:
            by_len: Dict[int, List] = {}
            for slot, req in pairs:
                by_len.setdefault(int(req.prompt.shape[0]), []).append((slot, req))
            groups = list(by_len.values())
        for group in groups:
            self._prefill_group(group)

    def _prefill_group(self, group) -> None:
        slots = [slot for slot, _ in group]
        reqs = [req for _, req in group]
        lens = [int(r.prompt.shape[0]) for r in reqs]
        S = max(lens)
        if self._pad_ok:
            # bucket the padded length to a power of two (>= 16) so the jit
            # compile set stays O(n_slots * log max_len) instead of one
            # program per distinct prompt length; extra pad KV is dead under
            # the causal mask once the cursor is rewound (see below)
            S = max(S, min(_pow2_bucket(S), self.max_len))
        prompts = jnp.stack([jnp.pad(r.prompt, (0, S - l))
                             for r, l in zip(reqs, lens)])       # (B, S)
        single = self.engine.new_cache(len(reqs), self.max_len)
        logits, single = self.engine.prefill(prompts, single)
        self.prefill_tokens += sum(lens)
        telemetry.count("sched.prefill_tokens", sum(lens))
        telemetry.count("sched.tokens", len(reqs))
        if S != min(lens) and "kv" in single:
            # rewind each slot's KV write cursor to its real prompt length:
            # pad KV beyond it is dead — overwritten by decode before the
            # positional mask ever exposes it (stub caches carry no cursor)
            single["kv"]["pos"] = jnp.broadcast_to(
                jnp.asarray(lens, jnp.int32)[None, :],
                single["kv"]["pos"].shape)
        self.cache = kv_cache.insert_slots(self.cache, single, slots)
        # ONE vectorized argmax + ONE host transfer for the first tokens
        lens_arr = jnp.asarray(lens, jnp.int32)
        firsts = jnp.argmax(
            logits[jnp.arange(len(reqs)), lens_arr - 1], axis=-1
        ).astype(jnp.int32)
        self.tokens = self.tokens.at[jnp.asarray(slots, jnp.int32)].set(firsts)
        for slot, req, l, first in zip(slots, reqs, lens,
                                       np.asarray(firsts).tolist()):
            req.slot = slot
            req.pos = l
            req.generated = [first]
            self.slots[slot] = req

    # -- paged admission -----------------------------------------------------
    def _match_prefix(self, tokens: List[int]) -> Tuple[List[int], int, bool]:
        """Trie lookup for an admitted prompt.

        Returns (shared physical pages, suffix start, cow) where the suffix
        ``tokens[suffix_start:]`` still needs a prefill.  A prompt fully
        covered by cached pages re-runs only its LAST token (the model must
        emit that token's logits), and because that write lands inside a
        shared page, the page is copy-on-write forked (``cow=True`` — the
        last matched page is the fork source, not shared)."""
        if self.trie is None:
            return [], 0, False
        matched = self.trie.match(tokens)
        if not matched:
            return [], 0, False
        if len(matched) * self.page_size == len(tokens):
            return matched, len(tokens) - 1, True
        return matched, len(matched) * self.page_size, False

    def _admit_paged(self) -> None:
        """Page-budgeted refill against the prefix trie, in sharing waves.

        Per candidate head: match the longest fully-cached page-aligned
        prefix, then reserve only the pages the request can still touch
        (suffix + worst-case decode) — ``PagePool.try_admit`` also pins the
        matched pages, so admission capacity is HBM pages, not slot count.
        Matched pages are never prefilled again: the group prefill gathers
        their KV straight out of the pool, runs ONLY the suffix tokens, and
        scatters the new KV into freshly allocated pages.  Decode pages are
        NOT pre-allocated here — ``step`` maps them lazily when a slot's
        cursor crosses a page boundary, against the admission reservation.

        A refill runs in **waves** so a classroom burst shares within one
        refill: a head about to prefill a page chunk that an earlier member
        of the current wave is already prefilling is deferred to the next
        wave, where the chunk has landed in the trie and is shared instead
        of recomputed — N simultaneous students still prefill the course
        prompt once.
        """
        while True:
            admitted, blocked = self._admit_wave()
            if not admitted or blocked:
                return

    def _admit_wave(self) -> Tuple[int, bool]:
        """One admission wave. Returns (n admitted, hard-blocked?) — hard
        blockage (page budget) ends the refill; a sharing deferral only ends
        the wave."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free:
            return 0, True
        P = self.page_size
        plan = []          # (slot, req, tokens, shared, suffix_start, cow_src)
        cow_pairs: List[Tuple[int, int]] = []
        wave_chunks: set = set()       # chunks being prefilled by this wave
        blocked = False
        for slot in free:
            req = self._next_request()
            if req is None:
                break
            tokens = [int(t) for t in np.asarray(req.prompt)]
            L = len(tokens)
            # the page table is max_pages wide: cap the decode budget so the
            # write cursor stays inside it (the dense layout silently clamp-
            # corrupts its tail past max_len; the paged layout must never
            # write into pages it doesn't own).  ``submit`` rejected prompts
            # with no decode room at all, so the cap is always >= 1.
            req.max_new = min(req.max_new, self.max_len - L)
            matched, suffix_start, cow = self._match_prefix(tokens)
            shared = matched[:-1] if cow else matched
            new_chunks = {tuple(tokens[i:i + P])
                          for i in range(len(matched) * P, L // P * P, P)}
            if new_chunks & wave_chunks:
                # an earlier wave member is prefilling this chunk: defer to
                # the next wave, where the trie will serve it
                self._put_back(req)
                break
            # worst-case write cursor: decode steps run while
            # len(generated) < max_new, writing at L .. L+max_new-2 (at least
            # one step always runs), so positions 0 .. L+max(max_new-1, 1)-1
            # must be page-covered
            total_pages = -(-(L + max(req.max_new - 1, 1)) // P)
            n_new = total_pages - len(shared)
            if not self.pool.try_admit(n_new, shared):
                # restore queue/inflight state BEFORE any raise: a popped
                # request must never be stranded
                self._put_back(req)
                if not any(s is not None for s in self.slots) and not plan:
                    # an empty batch could not fit it: permanently infeasible
                    raise ValueError(
                        f"request {req.rid} needs {n_new} pages but the pool "
                        f"can never free more than {self.pool.headroom()}")
                blocked = True
                break
            wave_chunks |= new_chunks
            self._tables[slot, :len(shared)] = shared
            # allocate the suffix's pages now (they are written this refill);
            # the rest of the reservation covers lazily mapped decode pages
            first_new = suffix_start // P
            n_prompt_pages = -(-L // P)
            for pi in range(first_new, n_prompt_pages):
                if cow and pi == first_new:
                    page = self.pool.cow()
                    cow_pairs.append((matched[-1], page))
                else:
                    page = self.pool.alloc_reserved()
                self._tables[slot, pi] = page
            self._slot_unreserved[slot] = n_new - (n_prompt_pages - first_new)
            self.shared_tokens += suffix_start
            telemetry.count("sched.shared_tokens", suffix_start)
            plan.append((slot, req, tokens, shared, suffix_start,
                         matched[-1] if cow else -1))
        if not plan:
            return 0, blocked
        paged = self.cache["paged"]
        if cow_pairs:
            # copy-on-write forks: duplicate each shared source page into the
            # slot-private target before any write can touch it (one batched
            # device copy per leaf for the whole refill)
            srcs = jnp.asarray([s for s, _ in cow_pairs], jnp.int32)
            tgts = jnp.asarray([t for _, t in cow_pairs], jnp.int32)
            paged = {
                **paged,
                "k_pages": paged["k_pages"].at[:, tgts].set(paged["k_pages"][:, srcs]),
                "v_pages": paged["v_pages"].at[:, tgts].set(paged["v_pages"][:, srcs]),
            }
        self.cache = {"paged": self._prefill_suffixes(paged, plan)}
        return len(plan), blocked

    def _prefill_suffixes(self, paged: Dict, plan) -> Dict:
        """ONE in-place suffix prefill for the admitted group.

        The paged flash-prefill kernel keeps the page table on the KV side
        of the grid, so the right-padded suffix tokens run ONE decode-shaped
        model call **directly against the pool**: shared prefix pages are
        read in place (no gather-copy into a transient dense cache) and the
        suffix KV lands in the freshly allocated pages through the model's
        own paged scatter.  Prefill FLOPs stay proportional to the UNMATCHED
        suffix.  Pad-token writes past a slot's prompt are routed to the
        trash page (position on an unmapped page) or land past the cursor in
        the slot-PRIVATE partial last page — the trie only ever retains FULL
        prompt pages, so shared pages are never written.
        """
        P = self.page_size
        slots = [p[0] for p in plan]
        lens = [len(p[2]) for p in plan]
        starts = [p[4] for p in plan]
        suf = [l - s for l, s in zip(lens, starts)]
        S = min(_pow2_bucket(max(suf)), max(self.max_len, max(suf)))
        B = len(plan)
        Ln = paged["pos"].shape[0]
        tbl_rows = jnp.asarray(self._tables[slots])                 # (B, MP)
        starts_dev = jnp.asarray(starts, jnp.int32)
        view = {"paged": {
            "k_pages": paged["k_pages"], "v_pages": paged["v_pages"],
            "table": jnp.broadcast_to(tbl_rows[None], (Ln, B, self.max_pages)),
            "pos": jnp.broadcast_to(starts_dev[None], (Ln, B))}}
        toks = jnp.stack([
            jnp.pad(jnp.asarray(p[2][p[4]:], jnp.int32), (0, S - (l - p[4])))
            for p, l in zip(plan, lens)])                           # (B, S)
        positions = starts_dev[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        logits, view = self.engine.decode(toks, positions, view)
        self.prefill_tokens += sum(suf)
        telemetry.count("sched.prefill_tokens", sum(suf))
        telemetry.count("sched.tokens", B)
        self.engine.n_prefill_tokens += B * S
        sl = jnp.asarray(slots, jnp.int32)
        paged = {
            **paged,
            "k_pages": view["paged"]["k_pages"],
            "v_pages": view["paged"]["v_pages"],
            "table": paged["table"].at[:, sl, :].set(tbl_rows[None]),
            "pos": paged["pos"].at[:, sl].set(
                jnp.asarray(lens, jnp.int32)[None]),
        }
        # register every full prompt page for future sharing (the trie takes
        # one retention ref per newly inserted page; matched chains are only
        # LRU-touched, so copy-on-write forks stay slot-private)
        if self.trie is not None:
            for slot, _req, tokens, _sh, _st, _cw in plan:
                chain = [int(p) for p in self._tables[slot, :len(tokens) // P]]
                for page in self.trie.insert(tokens, chain):
                    self.pool.retain_in_trie(page)
        # ONE vectorized argmax + ONE host transfer for the first tokens
        last = jnp.asarray([l - 1 - st for l, st in zip(lens, starts)], jnp.int32)
        firsts = jnp.argmax(
            logits[jnp.arange(B), last], axis=-1).astype(jnp.int32)
        self.tokens = self.tokens.at[jnp.asarray(slots, jnp.int32)].set(firsts)
        for (slot, req, tokens, _sh, _st, _cw), first in zip(
                plan, np.asarray(firsts).tolist()):
            req.slot = slot
            req.pos = len(tokens)
            req.generated = [first]
            self.slots[slot] = req
            # host copy of the prompt: the speculative draft engine replays
            # it (prompt + generated is each slot's full token history)
            self._host_prompt[slot] = tokens
            if self.draft is not None:
                # fresh slot: the draft's first catch-up feeds the prompt
                self.draft.reset([slot])
        self.peak_live = max(self.peak_live,
                             sum(1 for s in self.slots if s is not None))
        return paged

    def _map_decode_pages(self, horizon: int = 1) -> None:
        """Lazily map the pages each live slot's cursor will write within the
        next ``horizon`` positions (1 = plain decode; a speculative verify
        window maps its whole span up front).  The horizon is clamped to the
        slot's remaining decode budget so mapping never outruns the admission
        reservation — positions past the budget are routed to the trash page
        by the model's scatter and their rows are never emitted.  Pages come
        out of the reservation, so allocation can't fail; the device table
        is patched with ONE scatter."""
        upd: List[Tuple[int, int, int]] = []       # (slot, logical, physical)
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            budget = max(req.max_new - len(req.generated), 1)
            last = req.pos + min(horizon, budget) - 1
            for pi in range(req.pos // self.page_size,
                            last // self.page_size + 1):
                if self._tables[slot, pi] < 0:
                    page = self.pool.alloc_reserved()
                    self._slot_unreserved[slot] -= 1
                    assert self._slot_unreserved[slot] >= 0
                    self._tables[slot, pi] = page
                    upd.append((slot, pi, page))
        if upd:
            paged = self.cache["paged"]
            s = jnp.asarray([u[0] for u in upd], jnp.int32)
            li = jnp.asarray([u[1] for u in upd], jnp.int32)
            pg = jnp.asarray([u[2] for u in upd], jnp.int32)
            table = paged["table"].at[:, s, li].set(pg[None])
            self.cache = {"paged": {**paged, "table": table}}

    # -- one decode step over the whole batch --------------------------------
    def step(self) -> List[Request]:
        """Admit, then decode one token for every live slot.  The step's
        phases are telemetry spans: ``sched.admit`` (prefill inside),
        ``sched.map_pages``, ``sched.dispatch`` (the decode and the sampler
        enqueued), ``sched.wait`` (the host waits for the device and reads
        the tokens) and ``sched.update`` (the tokens' bookkeeping and
        teardown)."""
        with telemetry.span("sched.step"):
            return self._step()

    def _step(self) -> List[Request]:
        with telemetry.span("sched.admit"):
            self._admit()
        live = [s for s in self.slots if s is not None]
        if not live:
            return []
        self.peak_live = max(self.peak_live, len(live))
        telemetry.count("sched.decode_steps")
        telemetry.count("sched.live_slot_steps", len(live))
        if self.draft is not None:
            return self._spec_step()
        if self.paged:
            with telemetry.span("sched.map_pages"):
                self._map_decode_pages()
        with telemetry.span("sched.dispatch"):
            positions = jnp.array(
                [[s.pos if s is not None else 0] for s in self.slots],
                jnp.int32)
            logits, self.cache = self.engine.decode(
                self.tokens[:, None], positions, self.cache)
            self.key, sub = jax.random.split(self.key)
            nxt = sample(logits[:, -1], sub, self.sampler)
        with telemetry.span("sched.wait", cpu=True):
            # the host waits here for the device's step and reads every
            # slot's token in one transfer
            nxt = np.asarray(nxt)
        with telemetry.span("sched.update"):
            done_now: List[Request] = []
            for slot, req in enumerate(self.slots):
                if req is None:
                    continue
                tok = int(nxt[slot])
                req.generated.append(tok)
                req.pos += 1
                if tok == req.eos_id or len(req.generated) >= req.max_new:
                    req.done = True
                    done_now.append(req)
                    self.slots[slot] = None
                    self.user_inflight[req.user] = False
                else:
                    self.tokens = self.tokens.at[slot].set(tok)
            telemetry.count("sched.tokens", len(live))
            if done_now:
                self._teardown([r.slot for r in done_now])
            self.finished.extend(done_now)
        return done_now

    def _spec_step(self) -> List[Request]:
        """One speculative round: draft k tokens per slot, verify all k+1
        positions in ONE decode-shaped paged step, keep the longest agreeing
        prefix plus the verifier's correction/bonus token.

        Bit-exact with non-speculative greedy decoding: row j of the verify
        block computes exactly the logits the plain loop would compute at
        that position (same kernel family, same KV), and every emitted token
        is the VERIFIER's argmax — proposals only decide how many rows are
        consumed.  Rejected draft KV lands above the rewound cursors, where
        the scatter-then-attend discipline overwrites it before any query
        can see it, and the cursor rewind below makes the pages reusable
        immediately — nothing to release, no COW interaction.
        """
        K = self.spec_k
        items = [(slot, req, self._host_prompt[slot] + req.generated)
                 for slot, req in enumerate(self.slots) if req is not None]
        props = self.draft.propose(items, K)            # (n_slots, K)
        with telemetry.span("sched.map_pages"):
            self._map_decode_pages(horizon=K + 1)
        toks = np.zeros((self.n_slots, K + 1), np.int32)
        base = np.zeros(self.n_slots, np.int32)
        for slot, req, hist in items:
            toks[slot, 0] = hist[-1]                    # last emitted token
            toks[slot, 1:] = props[slot]
            base[slot] = req.pos
        t0 = time.monotonic()
        base_dev = jnp.asarray(base)
        positions = base_dev[:, None] + \
            jnp.arange(K + 1, dtype=jnp.int32)[None]
        logits, cache = self.engine.decode(jnp.asarray(toks), positions,
                                           self.cache)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        with telemetry.span("sched.wait", cpu=True):
            nxt = np.asarray(nxt)
        self.spec_stats["verify_time"] += time.monotonic() - t0
        self.spec_stats["rounds"] += 1
        done_now: List[Request] = []
        new_pos = np.zeros(self.n_slots, np.int32)
        for slot, req, hist in items:
            a = 0
            while a < K and props[slot, a] == nxt[slot, a]:
                a += 1
            emitted = 0
            for j in range(a + 1):
                tok = int(nxt[slot, j])
                req.generated.append(tok)
                req.pos += 1
                emitted += 1
                if tok == req.eos_id or len(req.generated) >= req.max_new:
                    req.done = True
                    break
            self.draft.commit(slot, a, K, len(hist) + emitted)
            self.spec_stats["proposed"] += K
            self.spec_stats["accepted"] += a
            self.spec_stats["emitted"] += emitted
            telemetry.count("sched.tokens", emitted)
            if req.done:
                done_now.append(req)
                self.slots[slot] = None
                self.user_inflight[req.user] = False
            new_pos[slot] = 0 if req.done else req.pos
        # the verify advanced every cursor by K+1; rewind to the true
        # host-side positions (accepted prefix + 1) — rejected draft KV is
        # stranded above the cursor and dead
        paged = cache["paged"]
        Ln = paged["pos"].shape[0]
        self.cache = {"paged": {**paged, "pos": jnp.broadcast_to(
            jnp.asarray(new_pos)[None], (Ln, self.n_slots))}}
        if done_now:
            self._teardown([r.slot for r in done_now])
        self.finished.extend(done_now)
        return done_now

    # -- streaming ------------------------------------------------------------
    def step_stream(self) -> List[Tuple[Request, List[int], bool]]:
        """One ``step()`` with per-request token deltas: returns
        ``(req, new_tokens, done)`` for every request that grew this step,
        in slot order.  A freshly admitted request's first event carries its
        prefill-argmax token (the TTFT token lands the step the slot is
        admitted, not when the request finishes); a speculative round's
        event carries the whole accepted prefix as one burst.  Concatenating
        a request's deltas across steps reproduces ``req.generated``
        exactly — streaming changes delivery, never tokens."""
        before = {s.rid: len(s.generated)
                  for s in self.slots if s is not None}
        done_now = self.step()
        grew = [s for s in self.slots if s is not None] + done_now
        events = []
        for req in sorted(grew, key=lambda r: r.slot):
            new = req.generated[before.get(req.rid, 0):]
            if new:
                events.append((req, list(new), req.done))
        return events

    def run_stream(self, max_steps: int = 10_000):
        """Generator over streaming events until every queue and slot
        drains — the streaming twin of ``run_to_completion``."""
        for _ in range(max_steps):
            if self.pending() == 0:
                return
            for event in self.step_stream():
                yield event

    def cancel(self, rid: int) -> bool:
        """Abort a request mid-decode (the client dropped its stream).

        A live slot is torn down immediately — dense KV reset or paged
        pages/reservation released back to the pool (and the draft cache
        forgotten) — and the user's in-flight mark cleared so their next
        queued request can admit.  A still-queued request is simply
        removed.  The partial ``req.generated`` is retained on the request
        (the proxy settles only those tokens).  Returns False for an
        unknown/already-finished rid."""
        for slot, req in enumerate(self.slots):
            if req is not None and req.rid == rid:
                req.done = True
                self.slots[slot] = None
                self.user_inflight[req.user] = False
                self._teardown([slot])
                self.finished.append(req)
                return True
        for user, q in self.queues.items():
            for r in list(q):
                if r.rid == rid:
                    q.remove(r)
                    return True
        return False

    def spec_summary(self) -> Dict:
        """Speculation telemetry for Metadata / proxy.stats(): acceptance
        rate, draft/verify wall time, emitted-per-round."""
        s = dict(self.spec_stats)
        if self.draft is not None:
            s["draft_time"] = self.draft.draft_time
        s["acceptance_rate"] = (s["accepted"] / s["proposed"]
                                if s["proposed"] else 0.0)
        s["tokens_per_round"] = (s["emitted"] / s["rounds"]
                                 if s["rounds"] else 0.0)
        return s

    def _teardown(self, slots: List[int]) -> None:
        """Batched end-of-step teardown: ONE masked pass (dense) or ONE
        table/cursor reset (paged) for every slot finished this step, plus
        page refcount release on the pool."""
        if not self.paged:
            self.cache = kv_cache.reset_slots(self.cache, slots)
            return
        if self.draft is not None:
            self.draft.reset(slots)
        for slot in slots:
            pages = self._tables[slot][self._tables[slot] >= 0]
            self.pool.release(pages.tolist(), int(self._slot_unreserved[slot]))
            self._tables[slot] = -1
            self._slot_unreserved[slot] = 0
            self._host_prompt.pop(slot, None)
        paged = self.cache["paged"]
        sl = jnp.asarray(slots, jnp.int32)
        self.cache = {"paged": {
            **paged,
            "table": paged["table"].at[:, sl, :].set(-1),
            "pos": paged["pos"].at[:, sl].set(0),
        }}

    def run_to_completion(self, max_steps: int = 10_000) -> List[Request]:
        for _ in range(max_steps):
            if self.pending() == 0:
                break
            self.step()
        return self.finished
