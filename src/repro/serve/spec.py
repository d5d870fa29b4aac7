"""Speculative decoding with page-exact rollback.

A ``SpecDecodeEngine`` is a ``ServeEngine`` whose decode phase runs a
second, smaller DRAFT model ahead of the target: each round the draft
proposes ``k`` tokens autoregressively (cheap — k small decode steps on a
small model), the TARGET scores all ``k + 1`` candidate positions in ONE
knee-certified batched verify pass (``models.lm.paged_verify``, bitwise
identical to ``k + 1`` sequential decode steps — see
``layers.attn_verify_paged``), and the longest agreeing prefix commits.
Emitted tokens are ALWAYS the target's own greedy argmaxes, so the output
stream is bitwise identical to non-speculative greedy decode no matter
what the draft proposes — the draft only controls how many tokens commit
per round (1 .. k + 1).

**Rejection is an arena truncation, never a requantization.**  The paged
int8 QTensor KV layout makes the rejected suffix page-exact to undo:
``PagePool.rollback_seq_len`` frees the tail pages (LIFO, so re-extension
re-claims exactly what a never-speculated pool would) and
``kvcache.truncate_pages`` zero-scrubs them plus the boundary page's
rejected slots — on fresh pages the arena is bitwise identical to one
that never appended, and the next committed token writes exactly the
first scrubbed slot under the unchanged page-scale discipline.  Both
lanes roll back: the target arena past the accepted length, the draft
arena to the same point.

**Two lanes, one scheduler.**  The draft runs its own paged arena +
``PagePool`` + ``AttnPlan`` through the same ``PagedModel`` protocol and
compile cache as the target.  Draft state is pure recompute — on
preemption it is dropped (not swapped: the swap bill stays the target's),
and a sequence re-primes lazily with a single one-shot ``final=False``
prefill of its committed tokens when it next enters a spec round.  Rows
that cannot reserve ``k + 1`` target pages (or a draft lane) fall back to
plain batched decode for that round, so speculative mode inherits the
base engine's no-livelock argument unchanged: the oldest resident always
progresses.

**Numerics contract.**  ``plan_verify`` re-certifies every bucket for the
(bucket, k) verify signatures: a verify batch widens the GEMM's row
count, never a row's accumulation length, so the §4.4 knee test and the
e_acc overflow bound hold at the bucket's already-certified worst case
(Blumenfeld et al., arXiv:2401.14110: keep the accumulator at the bound;
Colbert et al., arXiv:2301.13376: re-check overflow avoidance at the new
geometry).  Warmup covers draft prefill/decode, per-bucket verify, and
the fixed-width rollback scrub — steady-state spec serving performs zero
traces (gated in CI).

Acceptance-rate / rollback-depth counters flow through ``engine.events``
and ``repro.obs.metrics.record_spec_events`` (``repro_serve_spec_*``),
and every round emits ``draft`` / ``verify`` / ``rollback`` spans.
"""

from __future__ import annotations

from repro.models.api import DecodeRequest, PrefillRequest, VerifyRequest
from repro.obs.trace import span
from repro.serve.kvcache import PagedKVConfig, PagePool
from repro.serve.plan import plan_attention, plan_verify
from repro.serve.scheduler import ModelExecutor, ServeEngine, _Seq

__all__ = ["SpecDecodeEngine"]


class SpecDecodeEngine(ServeEngine):
    """Continuous-batching engine with a draft-model speculative lane."""

    def __init__(self, model, params, *, spec_k: int = 4,
                 draft_model=None, draft_params=None, draft_executor=None,
                 draft_n_pages: int | None = None, **kw):
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        warm = kw.pop("warm_start", False)
        super().__init__(model, params, warm_start=False, **kw)
        if self.tp_shards > 1:
            raise NotImplementedError(
                "speculative decoding is single-device for now (the draft "
                "lane and rollback scrub are not mesh-partitioned)")
        self.spec_k = spec_k
        ps = self.page_size
        if draft_n_pages is None:
            # headroom for every batch row's in-flight proposals, so the
            # draft lane under-pressures strictly less than the target
            draft_n_pages = self.n_pages \
                + self.max_batch * (-(-(spec_k + 1) // ps))
        if (draft_n_pages - 1) * ps < self.tokens_capacity + spec_k:
            raise ValueError(
                f"draft arena of {draft_n_pages} pages cannot hold a "
                f"max-length sequence plus {spec_k} proposals")
        if draft_executor is None:
            if draft_model is None:
                raise ValueError(
                    "SpecDecodeEngine needs draft_model+draft_params or an "
                    "injected draft_executor")
            dpc = PagedKVConfig.for_model(
                draft_model.cfg, n_pages=draft_n_pages, page_size=ps,
                kv_fmt=self.kv_fmt)
            draft_executor = ModelExecutor(
                draft_model, draft_params, dpc, kv_fmt=self.kv_fmt,
                oracle=self.oracle, max_batch=self.max_batch)
        self.draft_model = draft_model
        self.draft_executor = draft_executor
        self.draft_cfg = getattr(draft_executor, "cfg", None)
        self.draft_pool = PagePool(draft_n_pages, ps)
        # the draft lane prefills one-shot (no chunking: primes are single
        # calls, and draft numerics only steer proposal quality)
        self.draft_plan = plan_attention((draft_n_pages - 1) * ps, ps)
        self.verify_plan = plan_verify(self.plan, k=spec_k)
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.spec_rollback_tokens = 0
        self.draft_primes = 0
        self.fallback_rows = 0
        if self.metrics is not None:
            self._m_spec_acc = self.metrics.gauge(
                "repro_serve_spec_acceptance_rate",
                "cumulative accepted/proposed draft tokens")
        if warm:
            self.warmup()

    # ------------------------------ accounting -----------------------------
    def acceptance_rate(self) -> float:
        """Accepted / proposed draft tokens, cumulative."""
        return self.spec_accepted / max(self.spec_proposed, 1)

    # ------------------------------ warmup ---------------------------------
    def warmup(self) -> dict | None:
        """Base warmup plus the speculative lane's signatures: per-bucket
        (bucket, k) verify + the rollback scrub on the target executor,
        and the draft's per-bucket decode + one-shot ``final=False``
        prefill + rollback.  After this, spec-mode steady state performs
        zero traces."""
        out = super().warmup()
        wv = getattr(self.executor, "warmup_verify", None)
        if wv is not None:
            wv(self.plan, self.spec_k)
        dw = getattr(self.draft_executor, "warmup", None)
        if dw is not None:
            dw(self.draft_plan, None, prefill_finals=(False,))
            self.draft_executor.warmup_verify(self.draft_plan, self.spec_k,
                                              include_verify=False)
        return out

    # ------------------------------ lifecycle ------------------------------
    def preempt(self, rid: int) -> None:
        # draft state is pure recompute: drop it rather than doubling the
        # swap bill; the row re-primes lazily after restore
        if self.draft_pool.owns(rid):
            self.draft_pool.release(rid)
        super().preempt(rid)

    def _maybe_finish(self, seq: _Seq) -> bool:
        done = super()._maybe_finish(seq)
        if done and self.draft_pool.owns(seq.rid):
            self.draft_pool.release(seq.rid)
        return done

    # ------------------------------ draft lane -----------------------------
    def _drop_draft_younger_than(self, rid: int) -> bool:
        """Free draft pages by dropping the YOUNGEST other draft-resident
        row strictly younger than ``rid`` — rows older than ``rid`` are
        already committed to this round's spec batch and their draft state
        must survive.  Dropping is always safe (recompute)."""
        victims = [r for r in self.active
                   if r > rid and self.draft_pool.owns(r)]
        if not victims:
            return False
        self.draft_pool.release(max(victims))
        return True

    def _prime_draft(self, seq: _Seq) -> None:
        """One-shot ``final=False`` prefill of the row's committed tokens
        (all but the last — that one is the first verify input) into the
        draft arena."""
        rid, n = seq.rid, seq.pos
        dp = self.draft_pool
        pages = dp.allocate(rid, n)
        bucket_i, bucket = self.draft_plan.bucket_for(n)
        slab_w = bucket.max_ctx
        call = (self.draft_plan.kernel_call(
                    bucket_i, h=self.draft_cfg.n_heads,
                    dh=self.draft_cfg.head_dim, kv_fmt=self.kv_fmt,
                    slab_tokens=slab_w)
                if self.draft_cfg is not None else None)
        self.draft_executor.prefill(PrefillRequest(
            rid=rid, tokens=tuple(seq.tokens[:n]), hist_pages=(),
            slab_pages=tuple(pages), t0=0, acc=bucket.acc, final=False,
            bucket_pages=bucket.max_pages(self.page_size),
            slab_width=slab_w, call=call))
        self.draft_primes += 1

    def _draft_ready(self, seq: _Seq) -> int | None:
        """Make the draft lane able to carry ``seq`` through this round and
        CLAIM its pages up front (extended to ``pos + k`` now, so a later
        row's prime cannot steal the free pages this row's micro-steps
        need).  A lag of exactly 1 (the previous round accepted
        everything) is carried by a catch-up micro-step; a larger lag
        (plain-decode fallback rounds) drops + one-shot re-primes instead
        of token-by-token catch-up.  Returns the draft's cached length at
        round start (the first micro-step's write position), or None →
        the row falls back to plain decode this round."""
        rid, k = seq.rid, self.spec_k
        dp = self.draft_pool
        if dp.owns(rid) and dp.seq_len(rid) < seq.pos - 1:
            dp.release(rid)
        held = len(dp.pages(rid)) if dp.owns(rid) else 0
        want = dp.pages_for(seq.pos + k)
        while want - held > dp.free_pages:
            if not self._drop_draft_younger_than(rid):
                return None
        if not dp.owns(rid):
            self._prime_draft(seq)
        d0 = dp.seq_len(rid)
        dp.extend(rid, seq.pos + k - d0)
        return d0

    def _reserve_spec(self, seq: _Seq) -> int | None:
        """Claim the round's transient resources for one row: ``k + 1``
        target pages (the verify slab) + a ready draft lane.  In
        reservation mode the overshoot borrows FREE pages only (never
        another row's entitlement) and returns them at rollback within
        the same step, so ``free >= reserved`` holds at every step edge.
        Returns the draft-lane start position, or None on failure."""
        rid = seq.rid
        if self.reserve_admission:
            if not self.pool.can_extend(rid, 1 + self.spec_k):
                return None
        elif not self._ensure_pages(
                rid, self.pool.seq_len(rid) + 1 + self.spec_k):
            return None
        d0 = self._draft_ready(seq)
        if d0 is None:
            return None
        self.pool.extend(rid, 1 + self.spec_k)
        return d0

    # ------------------------------ rollback -------------------------------
    def _rollback(self, pool, executor, rid: int, keep: int,
                  old: int) -> int:
        """Truncate one lane's arena to ``keep`` cached tokens: pool tail
        pages freed + executor scrub (page-exact, bitwise never-appended
        on fresh pages).  Returns the rollback depth in tokens."""
        if keep >= old:
            return 0
        pages_old = pool.pages(rid)
        pool.rollback_seq_len(rid, keep)
        fn = getattr(executor, "rollback", None)
        if fn is not None:
            fn(rid, pages_old, keep, old)
        return old - keep

    # ------------------------------ decode ---------------------------------
    def _decode_batch(self) -> list[int]:
        """One spec round for every eligible running row + one plain decode
        for the rest.  Keeps the base engine's step discipline (<=1
        restore/admit, <=1 prefill slab per step around this)."""
        spec: list[tuple[_Seq, int]] = []
        plain: list[_Seq] = []
        for rid in sorted(self.active):
            seq = self.active.get(rid)
            if seq is None or seq.in_prefill:
                continue
            budget = seq.max_new - len(seq.generated)
            if budget >= 2:
                d0 = self._reserve_spec(seq)
                if d0 is not None:
                    spec.append((seq, d0))
                    continue
            # plain lane: the base engine's admission, token by token
            if self.reserve_admission:
                if not self.pool.can_extend(rid):
                    continue
            elif not self._ensure_pages(rid, self.pool.seq_len(rid) + 1):
                continue
            if self.active.get(rid) is None:
                continue
            self.pool.extend(rid)
            plain.append(seq)
            if budget >= 2:
                self.fallback_rows += 1
        finished: list[int] = []
        if spec:
            finished += self._spec_round(spec)
        if plain:
            finished += self._decode_rows(plain)
        if spec or plain:
            self._decode_steps += 1
            if self.monitor_cadence \
                    and self._decode_steps % self.monitor_cadence == 0:
                self._monitor()
        return finished

    def _propose(self, batch: list[tuple[_Seq, int]],
                 ) -> tuple[dict[int, list[int]], int]:
        """Draft phase: batched micro-steps until every row holds ``k``
        proposals.  The draft pool was already extended to ``pos + k`` at
        reserve time, so micro-steps only write — ``d0`` is each row's
        first write position.  A row whose draft lane started at
        ``pos - 1`` (previous round accepted everything) runs one catch-up
        step first — its output is discarded (the committed token is
        already known) — so a round costs ``k`` or ``k + 1`` draft decode
        steps, all on warmed (bucket-shaped) signatures."""
        k = self.spec_k
        props: dict[int, list[int]] = {s.rid: [] for s, _ in batch}
        cur: dict[int, int] = {s.rid: d0 for s, d0 in batch}
        steps = 0
        while True:
            live = [s for s, _ in batch if len(props[s.rid]) < k]
            if not live:
                return props, steps
            rows = []
            for s in live:
                q = cur[s.rid]  # this micro-step's write position
                cur[s.rid] = q + 1
                inp = (s.tokens[q] if q < len(s.tokens)
                       else props[s.rid][q - len(s.tokens)])
                rows.append((s, q, inp))
            # bucket by the round's PRE-EXTENDED draft extent (pos + k),
            # not this micro-step's attended length: the page table must
            # cover every page the pool already claimed for the round, and
            # it keeps all k micro-steps on ONE warmed decode signature
            _, bucket = self.draft_plan.bucket_for(
                max(self.draft_pool.seq_len(s.rid) for s, _, _ in rows))
            width = bucket.max_pages(self.page_size)
            pt = self.draft_pool.page_table(
                [s.rid for s, _, _ in rows], width)
            toks = self.draft_executor.decode(DecodeRequest(
                rids=tuple(s.rid for s, _, _ in rows),
                last_tokens=tuple(i for _, _, i in rows),
                page_table=tuple(tuple(r) for r in pt.tolist()),
                positions=tuple(q for _, q, _ in rows),
                seq_lens=tuple(q + 1 for _, q, _ in rows),
                acc=bucket.acc))
            steps += 1
            for (s, q, _), t in zip(rows, toks):
                if q >= s.pos:  # predicts index q+1, past the committed end
                    props[s.rid].append(int(t))

    def _spec_round(self, batch: list[tuple[_Seq, int]]) -> list[int]:
        """Draft k → verify k+1 → accept prefix → page-exact rollback."""
        k = self.spec_k
        rows = [s for s, _ in batch]
        rids = [s.rid for s in rows]
        with span(self.tracer, "draft", attrs={"rids": rids}, k=k) as sp:
            props, steps = self._propose(batch)
            if sp is not None:
                sp.attrs["steps"] = steps

        # target pool already extended to pos + k + 1 per row (_reserve_spec)
        _, bucket = self.verify_plan.bucket_for(
            max(self.pool.seq_len(r) for r in rids))
        width = bucket.max_pages(self.page_size)
        pt = self.pool.page_table(rids, width)
        with span(self.tracer, "verify", attrs={"rids": rids}, k=k):
            outs = self.executor.verify(VerifyRequest(
                rids=tuple(rids),
                tokens=tuple((s.tokens[-1], *props[s.rid]) for s in rows),
                page_table=tuple(tuple(r) for r in pt.tolist()),
                positions=tuple(s.pos for s in rows),
                seq_lens=tuple(s.pos + 1 for s in rows),
                acc=bucket.acc))
        if self.metrics is not None:
            self._m_decode.inc()

        finished: list[int] = []
        events = []
        for seq, u in zip(rows, outs):
            rid = seq.rid
            p = props[rid]
            m = 0
            while m < k and p[m] == u[m]:
                m += 1
            # u[:m] == the m accepted drafts; u[m] is the target's own next
            # token after them — emitted free, so every round commits >= 1
            emit = u[:m + 1]
            emit = emit[:seq.max_new - len(seq.generated)]
            if self.eos_id is not None and self.eos_id in emit:
                emit = emit[:emit.index(self.eos_id) + 1]
            n_e = len(emit)
            old_t = self.pool.seq_len(rid)           # pos + k + 1
            keep_t = seq.pos + n_e
            rb = self._rollback(self.pool, self.executor, rid, keep_t, old_t)
            old_d = self.draft_pool.seq_len(rid)     # pos + k
            keep_d = min(old_d, keep_t)
            self._rollback(self.draft_pool, self.draft_executor, rid,
                           keep_d, old_d)
            if rb and self.tracer is not None:
                h = self._spans.get(rid)
                self.tracer.end(self.tracer.start(
                    "rollback", parent=h["root"] if h else None,
                    trace_id=rid, depth=rb, ctx=keep_t))
            for t in emit:
                seq.tokens.append(int(t))
                seq.generated.append(int(t))
                self.decoded_tokens += 1
                self._obs_token(rid)
            self.spec_rounds += 1
            self.spec_proposed += k
            self.spec_accepted += m
            self.spec_emitted += n_e
            self.spec_rollback_tokens += rb
            events.append({
                "step": self._decode_steps, "event": "spec_round",
                "role": "serve", "rid": rid, "k": k, "proposed": k,
                "accepted": m, "emitted": n_e, "rollback_depth": rb,
                "ctx": keep_t,
            })
            if self._maybe_finish(seq):
                finished.append(rid)
        for e in events:
            self.events.append(e)
        if self.metrics is not None:
            from repro.obs.metrics import record_spec_events
            record_spec_events(self.metrics, events)
            self._m_spec_acc.set(self.acceptance_rate())
        return finished
