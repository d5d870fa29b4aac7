"""Continuous-batching scheduler over the paged QTensor KV-cache.

The static-batch serving loop (prefill a fixed batch, decode everyone to
the same horizon) wastes both axes: compute on sequences that finished
early, and KV memory sized for the longest request.  ``ServeEngine``
replaces it with the standard continuous-batching shape, plus the two
levers that keep utilization up under bursty, mixed-length arrivals:

* **chunked prefill** — a prompt is prefilled in ``prefill_chunk_tokens``
  slabs (page-aligned), ONE slab per engine step, interleaved with the
  batched decode of every running sequence — a long prompt no longer
  blocks the decode batch for a full step per prompt.  Each slab runs the
  resumable-carry ``flash_prefill`` (history carry-out pass over the
  sequence's pages, causal carry-in pass over the slab), which is
  bit-identical to the one-shot prefill at every split point — the
  numerics are scheduling-invariant by construction.
* **optimistic admission + preemption/swap** — admission asks only for the
  pages the FIRST prefill slab needs (not the worst-case final length), so
  the pool oversubscribes under load.  When a sequence cannot claim its
  next page, the engine preempts the YOUNGEST resident sequence: its
  packed int8 KV pages + per-page scale exponents are copied to a
  host-side ``SwapStore`` (they are already wire-format QTensor blocks, so
  swap is a copy, not a requantization) and its pages return to the pool.
  Swapped sequences are restored oldest-first as pages free up —
  allocation + byte-identical scatter, recompute-free — and resume
  mid-prefill (at a slab boundary) or mid-decode exactly where they left
  off.  The oldest resident sequence is never a victim, which is the
  no-livelock argument: it always progresses, completes, and frees pages
  for everyone behind it.  ``reserve_admission=True`` restores the old
  worst-case-reservation admission (no preemption) — the baseline the
  serve bench gates utilization against.

* **prefill / decode interleave** — each ``step()`` restores or admits at
  most one sequence, advances at most one prefill slab, then decodes ONE
  token for every running sequence in a single batched call of the paged
  flash-decode kernel — sequences at wildly different positions share the
  batch because every row carries its own position, page-table row and
  length;
* **eviction on completion** — a sequence hitting its token budget (or the
  optional EOS id) releases its pages back to the pool immediately.

Model execution is behind an executor seam: ``ModelExecutor`` runs the
real jitted model against the paged arena; the deterministic
``repro.serve.sim.SimExecutor`` replays the SAME scheduler against a
pure-host stamped arena, which is what lets ``tests/test_serve_sim.py``
fuzz hundreds of schedules (admission/preemption/swap orders, PagePool
invariants, token-loss/duplication, livelock) in seconds.

Accumulator widths come from the inference-side planner
(``repro.serve.plan``): each decode batch runs at the context bucket of
its LONGEST member (VRR is monotone in m_acc, so the shorter members are
strictly safe), and crossing a bucket edge re-jits at the wider format.

Serve-time VRR monitoring (``monitor_cadence``): every N decode steps the
longest context is probed with the stats variant of the decode kernel
(``collect_stats=True`` — the same ``EnsembleStats`` machinery as the
training-side telemetry).  The probed bucket is keyed by the GROWN
(post-decode) context length, not the original prompt length — a sequence
that decodes past its admission bucket's edge is re-planned at the bucket
its context is actually in.  The breach predicate is two-sided, because
the softmax-weighted ensemble is small and its carry-rounding NOISE can
inflate the measured variance ratio past 1 (the knee test's ``v = n2 (1 -
VRR)`` only sees deflation): (1) the MEASURED swamp rate — the fraction
of carry adds fully absorbed, the paper's swamping event counted directly
in-kernel — crossing ``swamp_threshold``, or (2) the closed-form knee
test failing at the context's ACTUAL grown length.  Either flags the
bucket and re-buckets it one mantissa bit wider instead of letting the
context swamp silently.  Events append to ``self.events`` (and the JSONL
log when given) in the training controller's schema dialect.
"""

from __future__ import annotations

from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.vrr import CUTOFF_LOG_V
from repro.models.api import (
    DecodeRequest,
    PrefillRequest,
    VerifyRequest,
    get_paged_model,
)
from repro.models.layers import LOCAL, Dist
from repro.obs.sink import RingBuffer, jsonl_append
from repro.obs.trace import span
from repro.quant.formats import FPFormat
from repro.serve.kvcache import (
    PagedKVConfig,
    PagePool,
    ShardedPagePool,
    SwapStore,
    init_arena,
    kv_bytes_per_token,
    swap_in_pages,
    swap_out_pages,
    truncate_pages,
)
from repro.serve.plan import (
    AttnPlan,
    certified_log_v,
    derive_v_hint,
    extra_carry_events,
    plan_attention,
)
from repro.telemetry.stats import EnsembleStats

__all__ = ["Request", "ModelExecutor", "ShardedModelExecutor", "ServeEngine",
           "measure_decode_vrr", "sharded_divergence"]


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int


@dataclass
class _Seq:
    rid: int
    tokens: list[int]          # prompt + generated
    prompt_len: int
    max_new: int
    generated: list[int] = field(default_factory=list)
    prefilled: int = 0         # prompt tokens whose KV is cached

    @property
    def pos(self) -> int:
        """Write position of the NEXT token's KV (= tokens cached so far)."""
        return len(self.tokens) - 1  # the last token's KV is not cached yet

    @property
    def in_prefill(self) -> bool:
        return self.prefilled < self.prompt_len

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new


@dataclass
class _Swapped:
    """A preempted sequence waiting in the SwapStore: its host-side page
    blob plus the cached-token count the blob covers (0 = preempted before
    its first slab claimed any pages).  ``final_pages`` carries the
    reservation-mode page entitlement across the swap, so a restore
    re-registers it and ``free >= reserved`` stays invariant."""

    seq: _Seq
    n_tokens: int
    final_pages: int | None = None


def measure_decode_vrr(kv_state, page_row: np.ndarray,
                       seq_len: int, *, cfg, kv_fmt: FPFormat,
                       acc: tuple[int, int], key) -> EnsembleStats:
    """Probe one context's decode-attention accumulator: a unit-Gaussian
    query (the telemetry probe's synthetic fallback posture —
    ``repro.telemetry.probe``) against the sequence's REAL layer-0 KV
    pages, through the stats variant of the decode kernel.  Returns the
    merged ``EnsembleStats`` window for the knee test."""
    from repro.kernels.attention import paged_attn_decode

    q = jax.random.normal(key, (1, cfg.n_heads, cfg.head_dim), jnp.float32)
    _, raw = paged_attn_decode(
        q, kv_state["k"][0], kv_state["v"][0],
        kv_state["k_se"][0], kv_state["v_se"][0],
        jnp.asarray(page_row[None]), jnp.asarray([seq_len], jnp.int32),
        kv_fmt=kv_fmt, acc=acc, collect_stats=True)
    return EnsembleStats.from_raw(np.asarray(raw))


# One compile cache per serve PROCESS, not per engine: tearing an engine
# down and constructing another with the same configuration (the bench's
# cold/warm pair, a restarted loop, tests sharing a model) re-uses every
# jitted executable instead of re-tracing.  Keyed on everything the traced
# computation closes over (config, formats, dist, padding widths);
# params/arena are operands, so engines with different weights share
# executables safely.  An unhashable configuration falls back to a private
# per-executor cache — sharing is lost, correctness is not.
_PROCESS_CACHE: dict = {}


def _device_topology() -> tuple:
    """The process's jax device topology, folded into every executor's
    compile-cache key: a cache entry describes executables compiled FOR a
    topology, so two executors in processes (or test monkeypatches) that
    see different device counts or platforms must not share one.  On a
    forced-host test process this is the
    ``--xla_force_host_platform_device_count`` value."""
    devices = jax.devices()
    return (len(devices), getattr(devices[0], "platform", "unknown"))


def _fresh_cache_entry() -> dict:
    return {"fns": {}, "stats": {"compiles": 0, "hits": 0, "misses": 0,
                                 "warm_compiles": 0}}


def process_cache_stats() -> dict:
    """Aggregate compile-cache traffic across every cached executor
    configuration in this process — the surface
    ``repro.obs.metrics.collect_process_metrics`` sweeps into the unified
    registry.  ``entries`` counts distinct cached configurations; the
    counter keys sum the per-entry ``compile_stats()`` dicts."""
    agg = {"entries": len(_PROCESS_CACHE), "compiles": 0, "hits": 0,
           "misses": 0, "warm_compiles": 0}
    for entry in _PROCESS_CACHE.values():
        for k, v in entry["stats"].items():
            agg[k] = agg.get(k, 0) + v
    return agg


class ModelExecutor:
    """Device-side executor: the real model + paged arena + compile cache.

    The engine core schedules in plain python (pages, slabs, victims); this
    class is the only place device work happens, which is also the seam the
    deterministic simulation executor (``repro.serve.sim.SimExecutor``)
    plugs into.  Both sides speak ONLY the ``repro.models.api`` paged
    protocol: ``prefill(PrefillRequest)`` / ``decode(DecodeRequest)``
    against a ``PagedModel``, with a process-wide compile cache whose
    jitted entries count their own traces — ``compile_stats()`` exposes
    compiles / dispatch hits / misses / warmup compiles, and the serve
    bench gates steady-state compiles at zero.

    ``prefill``/``decode`` are ``executor.*`` spans (``repro.obs.trace.
    span``), recorded in ``tracer`` when the engine installs one, and each
    jitted program is named ``serve_<kind>`` (``serve_decode``,
    ``serve_prefill``, ``serve_prefill_final``, ``serve_verify``,
    ``serve_rollback``), so a profile tells their device times apart.
    """

    tracer = None  # set by the ``ServeEngine`` that carries a tracer
    metrics = None  # likewise its metrics registry

    def __init__(self, model, params, pc: PagedKVConfig, *,
                 kv_fmt: FPFormat, dist: Dist = LOCAL, oracle: bool = False,
                 max_batch: int = 8):
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.pc = pc
        self.kv_fmt = kv_fmt
        self.dist = dist
        self.oracle = oracle
        self.max_batch = max_batch
        self.kv = self._init_arena(pc)
        self.pm = get_paged_model(model.cfg)
        key = self._cache_key()
        try:
            entry = _PROCESS_CACHE.get(key)
            if entry is None:
                entry = _PROCESS_CACHE[key] = _fresh_cache_entry()
        except TypeError:  # unhashable config: private, unshared cache
            entry = _fresh_cache_entry()
        self._cache = entry

    def _cache_key(self) -> tuple:
        """Everything the traced computations close over (config, formats,
        dist, padding widths) plus the device topology — params/arena are
        operands, so engines with different weights share executables, but
        executables compiled for a different device count or platform must
        not be dispatched against.  Subclasses append their own trace-
        relevant state (the sharded executor adds its mesh descriptor)."""
        return ("model-executor", self.cfg, self.kv_fmt, self.dist,
                self.oracle, self.max_batch, self.pc, _device_topology())

    def _init_arena(self, pc: PagedKVConfig) -> dict:
        return init_arena(pc)

    def _collectives(self, rows: int, scale_rows: int,
                     logit_rows: int) -> dict:
        """Stats of a call's ``executor.*`` span for its collectives: none
        on one device (see ``ShardedModelExecutor``)."""
        del rows, scale_rows, logit_rows
        return {}

    # ------------------------------ jit fns --------------------------------
    def _jit(self, key, fn, **jit_kw):
        """Memoized jit whose wrapped python body counts its own traces:
        the body runs exactly once per compiled signature (jax re-enters
        it only to trace), so ``stats["compiles"]`` is the compile count —
        including shape-driven retraces the key did not anticipate.  The
        program is named after the key's kind (``jit_serve_decode`` in the
        profile); a prefill that returns logits is ``serve_prefill_final``."""
        fns = self._cache["fns"]
        hit = fns.get(key)
        if hit is None:
            stats = self._cache["stats"]

            def counted(*a, **kw):
                stats["compiles"] += 1
                return fn(*a, **kw)

            final = key[0] == "prefill" and key[2]
            counted.__name__ = counted.__qualname__ = (
                f"serve_{key[0]}" + ("_final" if final else ""))
            hit = fns[key] = jax.jit(counted, **jit_kw)
        return hit

    def _decode_fn(self, acc: tuple[int, int]):
        import functools

        return self._jit(
            ("decode", acc, self.oracle),
            functools.partial(self.pm.decode, dist=self.dist,
                              kv_fmt=self.kv_fmt, acc=acc,
                              oracle=self.oracle))

    def _prefill_fn(self, acc: tuple[int, int], final: bool, call=None):
        # q_offset/q_len ride as traced int32 operands (no static_argnames):
        # every slab of every prompt in a bucket hits ONE compiled signature
        import functools

        key = (("prefill", call.static_signature(), final)
               if call is not None else ("prefill", acc, final))
        return self._jit(
            key,
            functools.partial(self.pm.prefill, dist=self.dist,
                              kv_fmt=self.kv_fmt, acc=acc, call=call,
                              want_logits=final))

    def _count_dispatch(self, before: int) -> None:
        stats = self._cache["stats"]
        if stats["compiles"] == before:
            stats["hits"] += 1
        else:
            stats["misses"] += 1

    # ------------------------------ engine ops -----------------------------
    def prefill(self, req: PrefillRequest) -> int | None:
        """Run one prefill slab; returns the first generated token on the
        final slab (greedy argmax of the last LIVE position's logits).

        Bucketed requests are padded to the bucket's compiled geometry:
        tokens to ``slab_width`` (zeros past ``q_len`` — projections are
        value-wise and the padded K/V rows are zeroed before the arena
        write, so the padding is byte-neutral), the page row to
        ``bucket_pages`` and the slab pages to the padded slab's page
        count (entry 0 = the reserved null page, never read under the
        kernel's ``q_len``/``kv_len`` mask)."""
        stats = self._cache["stats"]
        before = stats["compiles"]
        page_size = self.pc.page_size
        n_tok = len(req.tokens)
        width = req.slab_width or n_tok
        n_slab = -(-width // page_size)
        with span(self.tracer, "executor.prefill", tokens=n_tok,
                  **self._collectives(width, n_slab, int(req.final))):
            toks = np.zeros((1, width), np.int32)
            toks[0, :n_tok] = req.tokens
            n_hist = len(req.hist_pages)
            slab = np.zeros((n_slab,), np.int32)
            slab[:len(req.slab_pages)] = req.slab_pages
            row = np.zeros((req.bucket_pages or (n_hist + n_slab),),
                           np.int32)
            row[:n_hist] = req.hist_pages
            row[n_hist:n_hist + len(req.slab_pages)] = req.slab_pages
            logits, self.kv = self._prefill_fn(req.acc, req.final, req.call)(
                self.params, jnp.asarray(toks), self.kv, jnp.asarray(row),
                jnp.asarray(slab), jnp.int32(req.t0), jnp.int32(n_tok))
            self._count_dispatch(before)
            if not req.final:
                return None
            with span(self.tracer, "executor.prefill.wait"):
                return int(jnp.argmax(logits[0]))

    def decode(self, req: DecodeRequest) -> list[int]:
        """One batched decode token per row; returns the next tokens."""
        n = len(req.last_tokens)
        mb = self.max_batch
        with span(self.tracer, "executor.decode", rows=n,
                  **self._collectives(mb, mb, mb)):
            logits = self.decode_logits(req)
            with span(self.tracer, "executor.decode.wait"):
                out = np.asarray(jnp.argmax(logits[:n, 0], axis=-1))
        return [int(t) for t in out]

    def decode_logits(self, req: DecodeRequest) -> jnp.ndarray:
        """One batched decode step; returns the (max_batch, 1, vocab)
        logits (rows past the request's are padding) and advances the
        arena."""
        stats = self._cache["stats"]
        before = stats["compiles"]
        pt_in = np.asarray(req.page_table, np.int32)
        n, width = pt_in.shape
        # pad to max_batch so the jitted decode step keeps ONE shape per
        # (bucket, acc) as the active set breathes: padded rows are exact
        # no-ops (seq_len 0, null-page table row, write to page 0)
        pt = np.zeros((self.max_batch, width), np.int32)
        pt[:n] = pt_in
        tokens = np.zeros((self.max_batch, 1), np.int32)
        tokens[:n, 0] = req.last_tokens
        pos = np.zeros((self.max_batch,), np.int32)
        pos[:n] = req.positions
        sl = np.zeros((self.max_batch,), np.int32)
        sl[:n] = req.seq_lens
        logits, self.kv = self._decode_fn(req.acc)(
            self.params, jnp.asarray(tokens), self.kv, jnp.asarray(pt),
            jnp.asarray(pos), jnp.asarray(sl))
        self._count_dispatch(before)
        return logits

    def _verify_fn(self, acc: tuple[int, int], s_v: int):
        import functools

        if self.pm.verify is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no paged verify entry")
        return self._jit(
            ("verify", acc, s_v, self.oracle),
            functools.partial(self.pm.verify, dist=self.dist,
                              kv_fmt=self.kv_fmt, acc=acc,
                              oracle=self.oracle))

    def verify(self, req: VerifyRequest) -> list[list[int]]:
        """One batched speculative-verify step: scores ``s_v = k + 1``
        candidate tokens per row in a single knee-certified pass and
        returns each row's per-slab-index greedy argmax — entry ``j`` is
        the target's next token AFTER consuming the row's first ``j + 1``
        candidates, bitwise what ``s_v`` sequential ``decode`` calls
        would have returned.  Padding mirrors ``decode`` (max_batch rows,
        null-page tables, seq_len 0) so one compiled signature per
        (bucket, k) serves every request mix."""
        stats = self._cache["stats"]
        before = stats["compiles"]
        pt_in = np.asarray(req.page_table, np.int32)
        n, width = pt_in.shape
        s_v = len(req.tokens[0])
        pt = np.zeros((self.max_batch, width), np.int32)
        pt[:n] = pt_in
        tokens = np.zeros((self.max_batch, s_v), np.int32)
        tokens[:n] = req.tokens
        pos = np.zeros((self.max_batch,), np.int32)
        pos[:n] = req.positions
        sl = np.zeros((self.max_batch,), np.int32)
        sl[:n] = req.seq_lens
        logits, self.kv = self._verify_fn(req.acc, s_v)(
            self.params, jnp.asarray(tokens), self.kv, jnp.asarray(pt),
            jnp.asarray(pos), jnp.asarray(sl))
        self._count_dispatch(before)
        out = np.asarray(jnp.argmax(logits[:n], axis=-1))  # (n, s_v)
        return [[int(t) for t in row] for row in out]

    def rollback(self, rid: int, pages_old: list[int], keep_len: int,
                 old_len: int) -> None:
        """Page-exact rejection: scrub the arena slots of tokens
        ``keep_len..old_len-1`` (``kvcache.truncate_pages``) after the
        pool rolled the sequence back.  ``pages_old`` is the PRE-rollback
        page list.  The released-page operand is padded to a fixed width
        (``rollback_pad``, set by ``warmup_verify``) so every rollback
        dispatches ONE compiled signature."""
        del rid, old_len  # page-granular: pages_old + keep_len suffice
        page_size = self.pc.page_size
        n_keep = -(-keep_len // page_size)
        released = pages_old[n_keep:]
        keep_slots = keep_len % page_size
        boundary = pages_old[n_keep - 1] if keep_slots else 0
        pad = getattr(self, "rollback_pad", None)
        if pad is None:
            pad = self.rollback_pad = max(len(released), 1)
        if len(released) > pad:
            raise ValueError(
                f"rollback released {len(released)} pages > padded width "
                f"{pad} (warm with a larger k)")
        rel = np.zeros((pad,), np.int32)
        rel[:len(released)] = released
        stats = self._cache["stats"]
        before = stats["compiles"]
        self.kv = self._jit(("rollback", pad), truncate_pages)(
            self.kv, jnp.asarray(rel), jnp.int32(boundary),
            jnp.int32(keep_slots))
        self._count_dispatch(before)

    # ------------------------------ warmup ---------------------------------
    def warmup(self, plan: AttnPlan,
               prefill_chunk: int | None = None,
               prefill_finals: tuple[bool, ...] | None = None) -> dict:
        """Compile every certified bucket's kernels before traffic arrives
        (the ``warmup_gemm_autotune`` posture applied to serve compiles):
        for each bucket, the padded decode step and the padded prefill
        slab — final and, for multi-slab prompts, non-final — are CALLED
        on dummy operands with the exact shapes/dtypes the engine will
        use, because only a real call populates jit's dispatch cache (AOT
        lowering does not).  Outputs are discarded, so the arena is
        untouched.  After this, steady-state serving performs zero traces;
        ``compile_stats()["warm_compiles"]`` records what warmup paid."""
        stats = self._cache["stats"]
        before = stats["compiles"]
        page_size = self.pc.page_size
        for i, b in enumerate(plan.buckets):
            w = b.max_pages(page_size)
            self._decode_fn(b.acc)(
                self.params, jnp.zeros((self.max_batch, 1), jnp.int32),
                self.kv, jnp.zeros((self.max_batch, w), jnp.int32),
                jnp.zeros((self.max_batch,), jnp.int32),
                jnp.zeros((self.max_batch,), jnp.int32))
            slab_w = prefill_chunk or b.max_ctx
            call = plan.kernel_call(i, h=self.cfg.n_heads,
                                    dh=self.cfg.head_dim,
                                    kv_fmt=self.kv_fmt, slab_tokens=slab_w)
            finals = (list(prefill_finals) if prefill_finals is not None
                      else [True] + ([False] if prefill_chunk
                                     and b.max_ctx > prefill_chunk else []))
            n_slab = -(-slab_w // page_size)
            for final in finals:
                self._prefill_fn(b.acc, final, call)(
                    self.params, jnp.zeros((1, slab_w), jnp.int32),
                    self.kv, jnp.zeros((w,), jnp.int32),
                    jnp.zeros((n_slab,), jnp.int32),
                    jnp.int32(0), jnp.int32(slab_w))
        delta = stats["compiles"] - before
        stats["warm_compiles"] += delta
        return {"buckets": len(plan.buckets), "compiles": delta}

    def warmup_verify(self, plan: AttnPlan, k: int, *,
                      include_verify: bool = True) -> dict:
        """Compile the speculative lane's signatures before traffic: one
        ``(bucket, k)`` verify per bucket plus the single padded-width
        rollback scrub — after this, spec-mode steady state performs zero
        traces (the CI gate extends to spec on).  ``include_verify=False``
        warms only the rollback scrub — the DRAFT lane rolls back but is
        never verified, so its executor skips the per-bucket verify
        compiles."""
        stats = self._cache["stats"]
        before = stats["compiles"]
        page_size = self.pc.page_size
        s_v = k + 1
        self.rollback_pad = -(-s_v // page_size) + 1
        for b in plan.buckets if include_verify else ():
            w = b.max_pages(page_size)
            self._verify_fn(b.acc, s_v)(
                self.params, jnp.zeros((self.max_batch, s_v), jnp.int32),
                self.kv, jnp.zeros((self.max_batch, w), jnp.int32),
                jnp.zeros((self.max_batch,), jnp.int32),
                jnp.zeros((self.max_batch,), jnp.int32))
        self._jit(("rollback", self.rollback_pad), truncate_pages)(
            self.kv, jnp.zeros((self.rollback_pad,), jnp.int32),
            jnp.int32(0), jnp.int32(0))
        delta = stats["compiles"] - before
        stats["warm_compiles"] += delta
        return {"buckets": len(plan.buckets), "k": k, "compiles": delta}

    def compile_stats(self) -> dict:
        """Copy of the process compile-cache counters: ``compiles`` (jit
        traces), ``hits``/``misses`` (executor calls that did / did not
        trace), ``warm_compiles`` (traces paid during ``warmup``)."""
        return dict(self._cache["stats"])

    @contextmanager
    def compile_stats_scope(self):
        """Snapshot-delta view of the compile counters: yields a dict that
        is filled with the with-block's DELTA on exit.  Tests assert on the
        scoped delta instead of resetting the process-wide counters, so
        they compose under any pytest ordering."""
        before = dict(self._cache["stats"])
        delta: dict = {}
        try:
            yield delta
        finally:
            for k, v in self._cache["stats"].items():
                delta[k] = v - before.get(k, 0)

    def swap_out(self, rid: int, pages: list[int]) -> dict:
        return swap_out_pages(self.kv, pages)

    def swap_in(self, rid: int, pages: list[int], blob: dict) -> None:
        self.kv = swap_in_pages(self.kv, pages, blob)

    def measure_vrr(self, page_row: np.ndarray, ctx: int,
                    acc: tuple[int, int], key) -> EnsembleStats:
        return measure_decode_vrr(self.kv, page_row, ctx, cfg=self.cfg,
                                  kv_fmt=self.kv_fmt, acc=acc, key=key)


class ShardedModelExecutor(ModelExecutor):
    """Tensor-parallel executor over a 1-D ``model`` mesh: the SAME engine
    seam (``repro.models.api`` paged protocol), with every jitted entry
    wrapped in ``shard_map``.

    Partitioning is output-dim only (``sharding.specs.serve_param_specs``):
    attention heads and the KV arena's kv-head axis split across shards, so
    each shard owns its heads' COMPLETE online-softmax walks — identical
    block order and rounding to single-device — and the cross-shard merge is
    the exact psum'd carry combine (``kernels.attention.psum_carry``), whose
    neutral elements contribute exact zeros.  Sharded logits are therefore
    bitwise the single-device logits.  Page tables stay host-side and
    replicated: one logical allocator's page ids address every shard's
    arena slice (``ServeEngine`` pairs this executor with a
    ``ShardedPagePool`` that asserts per-shard allocator lockstep).

    ``logit_wire`` picks the unembed reduction: ``"gather"`` (exact —
    replicated head under tied embeddings, vocab-split + all_gather
    otherwise) or ``"int8"`` (``train.compression.compressed_psum``'s int8
    wire over d_model-partial logits — lossy in general, bit-exact only on
    lattice inputs; off by default).

    MoE models are rejected: ``moe_apply`` builds its OWN shard_map when a
    mesh is configured, and nesting it inside this executor's shard_map is
    not supported (``models.lm._check_shardable`` guards the model side).
    """

    def __init__(self, model, params, pc: PagedKVConfig, *,
                 kv_fmt: FPFormat, mesh=None, n_shards: int | None = None,
                 oracle: bool = False, max_batch: int = 8,
                 logit_wire: str = "gather"):
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_serve_mesh
        from repro.sharding.specs import named_shardings, serve_param_specs

        if mesh is None:
            mesh = make_serve_mesh(n_shards)
        if tuple(mesh.axis_names) != ("model",):
            raise ValueError(
                f"serve mesh must be 1-D over ('model',), got "
                f"{tuple(mesh.axis_names)}")
        s = mesh.shape["model"]
        cfg = model.cfg
        if logit_wire not in ("gather", "int8"):
            raise ValueError(f"unknown logit_wire {logit_wire!r}")
        if getattr(cfg, "moe", None) is not None:
            raise NotImplementedError(
                "ShardedModelExecutor does not support MoE models "
                "(moe_apply's own shard_map cannot nest)")
        for nm, dim in (("n_heads", cfg.n_heads),
                        ("n_kv_heads", cfg.n_kv_heads),
                        ("d_ff", cfg.d_ff)):
            if dim % s != 0:
                raise ValueError(
                    f"{s}-shard serve mesh cannot split {nm}={dim}")
        if logit_wire == "int8" and cfg.d_model % s != 0:
            raise ValueError(
                f"int8 logit wire slices d_model={cfg.d_model} across "
                f"{s} shards; not divisible")
        self.mesh = mesh
        self.n_shards = s
        self.logit_wire = logit_wire
        # serve_param_specs raises on any weight the mesh cannot split
        # (incl. untied lm_head vocab under the gather wire)
        self._pspecs = serve_param_specs(params, n_shards=s,
                                         logit_wire=logit_wire)
        self._kv_specs = {"k": P(None, None, "model"),
                          "v": P(None, None, "model"),
                          "k_se": P(), "v_se": P()}
        dist = Dist(shard_axis="model", tp_size=s, logit_wire=logit_wire)
        super().__init__(model, params, pc, kv_fmt=kv_fmt, dist=dist,
                         oracle=oracle, max_batch=max_batch)
        # commit params and arena onto the mesh up front: per-shard weight
        # slices and arena slices live on their shard, not re-sliced from a
        # replicated copy at every dispatch
        self.params = jax.device_put(
            self.params, named_shardings(self._pspecs, mesh))
        self.kv = jax.device_put(
            self.kv, named_shardings(self._kv_specs, mesh))

    def _cache_key(self) -> tuple:
        return super()._cache_key() + (
            ("mesh", tuple(self.mesh.shape.items()), self.logit_wire),)

    def _init_arena(self, pc: PagedKVConfig) -> dict:
        # made where it lives, each shard its KV-head slice: the whole
        # arena of a model that only fits sharded never exists on one chip
        from repro.sharding.specs import named_shardings

        return jax.jit(lambda: init_arena(pc), out_shardings=named_shardings(
            self._kv_specs, self.mesh))()

    def _collectives(self, rows: int, scale_rows: int,
                     logit_rows: int) -> dict:
        """The call's collective bytes on each shard, by kind
        (``models.lm.paged_collective_bytes``), counted in the registry's
        ``repro_serve_collective_bytes_total{kind}`` and returned as the
        span's ``shards`` and ``collective_bytes`` stats."""
        from repro.models.lm import paged_collective_bytes

        by_kind = paged_collective_bytes(self.cfg, self.dist, rows=rows,
                                         scale_rows=scale_rows,
                                         logit_rows=logit_rows)
        if self.metrics is not None:
            c = self.metrics.counter(
                "repro_serve_collective_bytes_total",
                "bytes the tensor-parallel executor's collectives leave on "
                "each shard, from shapes", labels=("kind",))
            for kind, nbytes in by_kind.items():
                c.inc(nbytes, kind=kind)
        return {"shards": self.n_shards,
                "collective_bytes": sum(by_kind.values())}

    def _decode_fn(self, acc: tuple[int, int]):
        import functools

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        inner = functools.partial(self.pm.decode, dist=self.dist,
                                  kv_fmt=self.kv_fmt, acc=acc,
                                  oracle=self.oracle)
        # check_vma=False: replication of the pmax'd page scales and the
        # all_gather'd activations is real but not provable by the checker
        fn = shard_map(
            inner, mesh=self.mesh,
            in_specs=(self._pspecs, P(), self._kv_specs, P(), P(), P()),
            out_specs=(P(), self._kv_specs), check_vma=False)
        return self._jit(("decode", acc, self.oracle), fn)

    def _prefill_fn(self, acc: tuple[int, int], final: bool, call=None):
        import functools

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        key = (("prefill", call.static_signature(), final)
               if call is not None else ("prefill", acc, final))
        inner = functools.partial(self.pm.prefill, dist=self.dist,
                                  kv_fmt=self.kv_fmt, acc=acc, call=call,
                                  want_logits=final)
        fn = shard_map(
            inner, mesh=self.mesh,
            in_specs=(self._pspecs, P(), self._kv_specs, P(), P(), P(),
                      P()),
            out_specs=(P(), self._kv_specs), check_vma=False)
        return self._jit(key, fn)

    def _verify_fn(self, acc: tuple[int, int], s_v: int):
        import functools

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        if self.pm.verify is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no paged verify entry")
        inner = functools.partial(self.pm.verify, dist=self.dist,
                                  kv_fmt=self.kv_fmt, acc=acc,
                                  oracle=self.oracle)
        fn = shard_map(
            inner, mesh=self.mesh,
            in_specs=(self._pspecs, P(), self._kv_specs, P(), P(), P()),
            out_specs=(P(), self._kv_specs), check_vma=False)
        return self._jit(("verify", acc, s_v, self.oracle), fn)


class ServeEngine:
    """Continuous-batching serving over one model's paged KV arena."""

    def __init__(
        self,
        model,
        params,
        *,
        n_pages: int,
        page_size: int,
        kv_fmt: FPFormat | None = None,
        plan: AttnPlan | None = None,
        max_batch: int = 8,
        eos_id: int | None = None,
        prefill_chunk_tokens: int | None = None,
        reserve_admission: bool = False,
        monitor_cadence: int = 0,
        monitor_log: str | None = None,
        swamp_threshold: float = 0.15,
        v_hint: float | None = None,
        oracle: bool = False,
        dist: Dist = LOCAL,
        seed: int = 0,
        executor=None,
        warm_start: bool = False,
        tracer=None,
        metrics=None,
        events_capacity: int | None = 4096,
    ):
        if prefill_chunk_tokens is not None:
            if prefill_chunk_tokens <= 0 \
                    or prefill_chunk_tokens % page_size != 0:
                raise ValueError(
                    f"prefill_chunk_tokens {prefill_chunk_tokens} must be a "
                    f"positive multiple of page_size {page_size}: slab "
                    "boundaries must land on page (carry-block) edges for "
                    "the resumed walk to be bit-identical to one-shot "
                    "prefill")
        self.model = model
        self.cfg = model.cfg if model is not None else None
        self.params = params
        self.kv_fmt = kv_fmt or FPFormat(e=5, m=2)
        self.page_size = page_size
        self.n_pages = n_pages
        self.tokens_capacity = (n_pages - 1) * page_size
        if executor is None:
            self.pc = PagedKVConfig.for_model(
                self.cfg, n_pages=n_pages, page_size=page_size,
                kv_fmt=self.kv_fmt)
            executor = ModelExecutor(model, params, self.pc,
                                     kv_fmt=self.kv_fmt, dist=dist,
                                     oracle=oracle, max_batch=max_batch)
        else:
            self.pc = getattr(executor, "pc", None)
        self.executor = executor
        if isinstance(executor, ModelExecutor):
            if tracer is not None:
                executor.tracer = tracer  # its executor.* spans join ticks'
            if metrics is not None:
                executor.metrics = metrics
        # tensor-parallel executors advertise their shard count; the engine
        # then allocates through a ShardedPagePool (one logical allocator,
        # N mirrored per-shard pools with lockstep assertions) and the plan
        # certifies the cross-shard reduction stage
        self.tp_shards = int(getattr(executor, "n_shards", 1) or 1)
        self.pool = (ShardedPagePool(n_pages, page_size,
                                     n_shards=self.tp_shards, tracer=tracer)
                     if self.tp_shards > 1 else PagePool(n_pages, page_size))
        self.store = SwapStore()
        self.plan = plan or plan_attention(
            self.tokens_capacity, page_size,
            prefill_chunk_tokens=prefill_chunk_tokens,
            tp_shards=self.tp_shards, v_hint=v_hint)
        self.max_batch = max_batch
        self.eos_id = eos_id
        self.prefill_chunk = prefill_chunk_tokens
        self.reserve_admission = reserve_admission
        self.monitor_cadence = monitor_cadence
        self.monitor_log = monitor_log
        self.swamp_threshold = swamp_threshold
        self.oracle = oracle
        self._key = jax.random.PRNGKey(seed)

        # observability (all optional): with tracer/metrics None every
        # guarded block below is skipped and ``span`` scopes only enter a
        # profiler annotation — the engine's schedule and model calls are
        # bit-identical to an uninstrumented build (pinned in
        # tests/test_obs_spans.py).  ``events`` is ring-buffered so
        # monitor/preempt/restore records cannot grow without bound on a
        # long-lived engine (events_capacity=None restores the old
        # unbounded behavior).
        self.tracer = tracer
        self.metrics = metrics
        self._spans: dict[int, dict] = {}  # rid -> {root, queued, swapped}
        if metrics is not None:
            self._init_metrics(metrics)

        self.pending: deque[Request] = deque()
        self.active: dict[int, _Seq] = {}
        self.swapped: dict[int, _Swapped] = {}
        self.finished: dict[int, list[int]] = {}
        self.events: RingBuffer = RingBuffer(events_capacity)
        self._next_rid = 0
        self._final_pages: dict[int, int] = {}   # reservation mode only
        self._decode_steps = 0
        self.steps = 0
        self.decoded_tokens = 0
        self.prefill_slabs = 0
        self.preemptions = 0
        self.restores = 0
        self.max_concurrent = 0
        if warm_start:
            self.warmup()

    @property
    def kv(self):
        """The executor's arena (compat accessor for benches/tests)."""
        return getattr(self.executor, "kv", None)

    # ------------------------------ compile cache ---------------------------
    def warmup(self) -> dict | None:
        """Compile every certified bucket's prefill/decode kernels up front
        so steady-state serving performs zero traces.  A no-op (returns
        None) for executors without a compile cache, e.g. the sim."""
        fn = getattr(self.executor, "warmup", None)
        return fn(self.plan, self.prefill_chunk) if fn is not None else None

    def compile_stats(self) -> dict | None:
        """The executor's compile-cache counters (None for the sim)."""
        fn = getattr(self.executor, "compile_stats", None)
        return fn() if fn is not None else None

    # ------------------------------ observability ---------------------------
    def _init_metrics(self, registry) -> None:
        """Register this engine's metric surface on ``registry`` (see README
        "Observability" for the naming convention)."""
        c, g, h = registry.counter, registry.gauge, registry.histogram
        self._m_tokens = c("repro_serve_tokens_total",
                           "generated tokens (first token + decode)")
        self._m_slabs = c("repro_serve_prefill_slabs_total",
                          "prefill slabs executed")
        self._m_preempt = c("repro_serve_preemptions_total",
                            "sequences swapped out under page pressure")
        self._m_restore = c("repro_serve_restores_total",
                            "swapped sequences swapped back in")
        self._m_decode = c("repro_serve_decode_steps_total",
                           "batched decode steps executed")
        self._m_done = c("repro_serve_requests_finished_total",
                         "requests run to completion")
        self._m_free = g("repro_serve_free_pages", "free KV pages")
        self._m_active = g("repro_serve_active_sequences",
                           "resident sequences")
        self._m_pending = g("repro_serve_pending_requests",
                            "submitted, not yet admitted")
        self._m_swapped = g("repro_serve_swapped_sequences",
                            "preempted sequences awaiting restore")
        self._m_ttft = h("repro_serve_ttft_seconds",
                         "time to first token (clock units)")
        self._m_tpot = h("repro_serve_tpot_seconds",
                         "mean inter-token gap (clock units)")

    def _obs_token(self, rid: int) -> None:
        """One emitted token: a ``token`` event on the request's root span
        plus the token counter."""
        if self.tracer is not None:
            h = self._spans.get(rid)
            if h is not None:
                self.tracer.event(h["root"], "token")
        if self.metrics is not None:
            self._m_tokens.inc()

    def _obs_finish(self, rid: int) -> None:
        """Close the request's span tree and record its TTFT/TPOT."""
        if self.metrics is not None:
            self._m_done.inc()
        if self.tracer is None:
            return
        h = self._spans.pop(rid, None)
        if h is None:
            return
        for key in ("queued", "swapped"):
            child = h.get(key)
            if child is not None and child.open:
                self.tracer.end(child)
        root = self.tracer.end(
            h["root"], tokens=len(self.finished.get(rid, ())))
        if self.metrics is not None:
            from repro.obs.trace import request_latencies
            for lat in request_latencies([root]):
                self._m_ttft.observe(lat["ttft"])
                if lat["tpot"] is not None:
                    self._m_tpot.observe(lat["tpot"])

    # ------------------------------ intake ---------------------------------
    def submit(self, prompt: list[int], max_new: int) -> int:
        need = self.pool.pages_for(len(prompt) + max_new)
        if need > self.n_pages - 1:
            raise ValueError(
                f"request of {len(prompt)} + {max_new} tokens needs {need} "
                f"pages; the pool holds {self.n_pages - 1} — it can never "
                "be served, with or without preemption")
        rid = self._next_rid
        self._next_rid += 1
        self.pending.append(Request(rid, list(prompt), max_new))
        if self.tracer is not None:
            root = self.tracer.start("request", trace_id=rid,
                                     prompt_len=len(prompt), max_new=max_new)
            self._spans[rid] = {
                "root": root,
                "queued": self.tracer.start("queued", parent=root),
                "swapped": None,
            }
        return rid

    # ------------------------------ admission ------------------------------
    def _admit_one(self) -> int | None:
        """Move at most one pending request into the active set.  Swapped
        sequences are strictly older, so while any wait, no NEW request is
        admitted (anti-starvation: restore-before-admit)."""
        if not self.pending or self.swapped \
                or len(self.active) >= self.max_batch:
            return None
        req = self.pending[0]
        if self.reserve_admission:
            # reservation admission: admit only when the free pool minus
            # every active sequence's OUTSTANDING reservation (pages it is
            # entitled to claim before finishing) covers this sequence at
            # its full final length.  Conservative — page pressure delays
            # admission — but needs no preemption path to be deadlock-free.
            need = self.pool.pages_for(len(req.prompt) + req.max_new)
            if self.pool.free_pages - self._reserved_outstanding() < need:
                return None
            self._final_pages[req.rid] = need
        else:
            # optimistic admission: ask only for the first prefill slab's
            # pages; growth past that is the preemption path's problem
            first = min(self.prefill_chunk or len(req.prompt),
                        len(req.prompt))
            if self.pool.free_pages < self.pool.pages_for(first):
                return None
        self.pending.popleft()
        self.active[req.rid] = _Seq(
            rid=req.rid, tokens=list(req.prompt),
            prompt_len=len(req.prompt), max_new=req.max_new)
        if self.tracer is not None:
            h = self._spans.get(req.rid)
            if h is not None and h["queued"] is not None:
                self.tracer.end(h["queued"])
                h["queued"] = None
        return req.rid

    def _reserved_outstanding(self) -> int:
        """Pages active sequences are still entitled to claim (reservation
        mode).  Held pages only convert reservations 1:1, so ``free >=
        reserved`` is invariant — every admitted sequence can always run to
        its final length."""
        return sum(
            max(self._final_pages[sid]
                - (len(self.pool.pages(sid)) if self.pool.owns(sid) else 0),
                0)
            for sid in self.active)

    # ------------------------------ preemption -----------------------------
    def preempt(self, rid: int) -> None:
        """Swap one resident sequence out: its packed pages + scale
        exponents move to the host-side SwapStore byte-identically, its
        pages return to the pool, and it queues for an oldest-first
        restore.  Public so the fuzz harness can force arbitrary
        preemption points; the engine itself calls it with the
        youngest-victim policy in ``_ensure_pages``."""
        seq = self.active.pop(rid)
        if self.pool.owns(rid):
            n_tok = self.pool.seq_len(rid)
            blob = self.executor.swap_out(rid, self.pool.pages(rid))
            self.store.put(rid, blob, n_tok)
            self.pool.release(rid)
        else:
            n_tok = 0  # preempted before its first slab claimed pages
        self.swapped[rid] = _Swapped(
            seq=seq, n_tokens=n_tok,
            final_pages=self._final_pages.pop(rid, None))
        self.preemptions += 1
        self.events.append({
            "step": self._decode_steps, "event": "preempt", "role": "serve",
            "rid": rid, "ctx": n_tok, "free_pages": self.pool.free_pages,
        })
        if self.tracer is not None:
            h = self._spans.get(rid)
            if h is not None:
                h["swapped"] = self.tracer.start("swapped", parent=h["root"],
                                                 ctx=n_tok)
        if self.metrics is not None:
            self._m_preempt.inc()

    def _ensure_pages(self, rid: int, new_len: int) -> bool:
        """Make the pool able to grow ``rid`` to ``new_len`` tokens,
        preempting strictly-YOUNGER residents (youngest first) as needed.
        If ``rid`` is itself the youngest and still short it STALLS —
        keeps its pages, skips this step, retries next tick (cheaper than
        swapping itself out, and safe: any older sequence that needs its
        pages will evict it).  The oldest resident is never a victim and
        never stalls — it can always claim from everyone younger — so it
        always progresses, completes, and frees pages: the engine cannot
        livelock.  Returns False on a stall."""
        held = len(self.pool.pages(rid)) if self.pool.owns(rid) else 0
        need = self.pool.pages_for(new_len) - held
        while need > self.pool.free_pages:
            victim = max((r for r in self.active if r > rid), default=None)
            if victim is None:
                return False
            self.preempt(victim)
        return True

    def _restore_one(self) -> int | None:
        """Re-admit the OLDEST swapped sequence once its pages fit:
        allocation + byte-identical scatter of the stored blob
        (recompute-free), resuming mid-prefill or mid-decode exactly where
        it was preempted."""
        if not self.swapped or len(self.active) >= self.max_batch:
            return None
        rid = min(self.swapped)
        ent = self.swapped[rid]
        if ent.final_pages is not None:
            # reservation mode (the engine itself never preempts here, but
            # the public preempt() may have): re-admit under the same
            # worst-case entitlement so ``free >= reserved`` stays true
            if self.pool.free_pages - self._reserved_outstanding() \
                    < ent.final_pages:
                return None
        elif ent.n_tokens and \
                self.pool.free_pages < self.pool.pages_for(ent.n_tokens):
            return None
        if ent.n_tokens:
            pages = self.pool.allocate(rid, ent.n_tokens)
            blob, _ = self.store.take(rid)
            self.executor.swap_in(rid, pages, blob)
        if ent.final_pages is not None:
            self._final_pages[rid] = ent.final_pages
        del self.swapped[rid]
        self.active[rid] = ent.seq
        self.restores += 1
        self.events.append({
            "step": self._decode_steps, "event": "restore", "role": "serve",
            "rid": rid, "ctx": ent.n_tokens,
            "free_pages": self.pool.free_pages,
        })
        if self.tracer is not None:
            h = self._spans.get(rid)
            if h is not None and h["swapped"] is not None:
                self.tracer.end(h["swapped"])
                h["swapped"] = None
        if self.metrics is not None:
            self._m_restore.inc()
        return rid

    # ------------------------------ prefill --------------------------------
    def _prefill_slab(self) -> int | None:
        """Advance the OLDEST prefilling sequence by one slab (at most one
        slab per engine step keeps the running batch's decode latency
        bounded).  The final slab yields the first generated token."""
        rid = next((r for r in sorted(self.active)
                    if self.active[r].in_prefill), None)
        if rid is None:
            return None
        seq = self.active[rid]
        t0 = seq.prefilled
        t1 = min(t0 + (self.prefill_chunk or seq.prompt_len), seq.prompt_len)
        if not self.reserve_admission:
            if not self._ensure_pages(rid, t1):
                return None  # stalled; retries this slab next step
        final = t1 == seq.prompt_len
        # the slab runs at the FULL prompt's bucket — every query row's
        # carry format must match the one-shot walk for bit-exactness
        bucket_i, bucket = self.plan.bucket_for(seq.prompt_len)
        h = self._spans.get(rid)
        with span(self.tracer, "serve.prefill_slab",
                  parent=h["root"] if h else None, trace_id=rid,
                  attrs={"t0": t0, "t1": t1, "bucket": bucket_i},
                  tokens=t1 - t0, final=final):
            if self.pool.owns(rid):
                self.pool.extend(rid, t1 - t0)
            else:
                self.pool.allocate(rid, t1)
            pages = self.pool.pages(rid)
            n_hist = t0 // self.page_size
            slab_w = self.prefill_chunk or bucket.max_ctx
            call = (self.plan.kernel_call(
                        bucket_i, h=self.cfg.n_heads, dh=self.cfg.head_dim,
                        kv_fmt=self.kv_fmt, slab_tokens=slab_w)
                    if self.cfg is not None else None)
            tok = self.executor.prefill(PrefillRequest(
                rid=rid, tokens=tuple(seq.tokens[t0:t1]),
                hist_pages=tuple(pages[:n_hist]),
                slab_pages=tuple(pages[n_hist:]), t0=t0, acc=bucket.acc,
                final=final, bucket_pages=bucket.max_pages(self.page_size),
                slab_width=slab_w, call=call))
            if self.metrics is not None:
                self._m_slabs.inc()
            seq.prefilled = t1
            self.prefill_slabs += 1
            if final:
                seq.tokens.append(int(tok))
                seq.generated.append(int(tok))
                self._obs_token(rid)
                self._maybe_finish(seq)
        return rid

    # ------------------------------ decode ---------------------------------
    def _decode_batch(self) -> list[int]:
        """One decode token for every running (fully prefilled) sequence."""
        batch: list[_Seq] = []
        for rid in sorted(self.active):
            seq = self.active.get(rid)
            if seq is None or seq.in_prefill:
                continue  # preempted as a victim this step, or still filling
            if self.reserve_admission:
                if not self.pool.can_extend(rid):
                    continue  # unreachable under reservation; defensive skip
            elif not self._ensure_pages(rid, self.pool.seq_len(rid) + 1):
                continue  # stalled (it is the youngest); retries next step
            self.pool.extend(rid)
            batch.append(seq)
        if not batch:
            return []
        finished = self._decode_rows(batch)
        self._decode_steps += 1
        if self.monitor_cadence and self._decode_steps % self.monitor_cadence == 0:
            self._monitor()
        return finished

    def _decode_rows(self, batch: list[_Seq]) -> list[int]:
        """One batched decode call for ``batch`` (pool pages already
        extended): a token for each row, bookkept; returns the rids that
        finished."""
        rids = [s.rid for s in batch]
        # engine-level: one decode step batches many requests, so no
        # trace_id — the rids attr links it to the request trees
        with span(self.tracer, "serve.decode_step", attrs={"rids": rids},
                  rows=len(batch)):
            _, bucket = self.plan.bucket_for(
                max(self.pool.seq_len(r) for r in rids))
            width = bucket.max_pages(self.page_size)
            pt = self.pool.page_table(rids, width)
            next_toks = self.executor.decode(DecodeRequest(
                rids=tuple(rids),
                last_tokens=tuple(s.tokens[-1] for s in batch),
                page_table=tuple(tuple(r) for r in pt.tolist()),
                positions=tuple(s.pos for s in batch),
                seq_lens=tuple(s.pos + 1 for s in batch), acc=bucket.acc))
            if self.metrics is not None:
                self._m_decode.inc()
            finished = []
            for seq, tok in zip(batch, next_toks):
                seq.tokens.append(int(tok))
                seq.generated.append(int(tok))
                self.decoded_tokens += 1
                self._obs_token(seq.rid)
                if self._maybe_finish(seq):
                    finished.append(seq.rid)
        return finished

    def _maybe_finish(self, seq: _Seq) -> bool:
        if seq.done or (self.eos_id is not None
                        and seq.generated and seq.generated[-1] == self.eos_id):
            self.finished[seq.rid] = list(seq.generated)
            self.pool.release(seq.rid)
            del self.active[seq.rid]
            self._final_pages.pop(seq.rid, None)
            if self.tracer is not None or self.metrics is not None:
                self._obs_finish(seq.rid)
            return True
        return False

    # ------------------------------ stepping -------------------------------
    def step(self) -> dict:
        """One engine tick: <=1 restore-or-admission, <=1 prefill slab, one
        batched decode."""
        with span(self.tracer, "serve.step"):
            self.steps += 1
            with span(self.tracer, "serve.admit"):
                restored = self._restore_one()
                admitted = self._admit_one() if restored is None else None
            self.max_concurrent = max(self.max_concurrent, len(self.active))
            prefilled = self._prefill_slab()
            finished = self._decode_batch() if self.active else []
            if self.metrics is not None:
                self._m_free.set(self.pool.free_pages)
                self._m_active.set(len(self.active))
                self._m_pending.set(len(self.pending))
                self._m_swapped.set(len(self.swapped))
        return {"admitted": admitted, "restored": restored,
                "prefilled": prefilled, "finished": finished,
                "active": len(self.active), "pending": len(self.pending),
                "swapped": len(self.swapped),
                "free_pages": self.pool.free_pages}

    def run(self, max_steps: int = 100_000) -> dict[int, list[int]]:
        """Drive to completion; returns {rid: generated tokens}."""
        for _ in range(max_steps):
            if not self.pending and not self.active and not self.swapped:
                break
            self.step()
        else:
            raise RuntimeError("serve loop did not drain (pool too small "
                               "for the pending prompts?)")
        return dict(self.finished)

    # ------------------------------ monitor --------------------------------
    def _monitor(self) -> None:
        """Swamping probe on the longest running context; a breach
        (measured swamp rate or the closed-form knee test at the grown
        length — see module docstring) re-buckets rather than letting the
        context swamp.  The bucket is keyed by the GROWN context length: a
        sequence that decodes past its admission bucket's edge is
        re-planned at the bucket its context is actually in, not the one
        its original prompt length fell into.

        The closed-form side runs through the MEMOIZED bucket-wide
        certification (``plan.certified_log_v`` at the bucket's
        ``max_ctx`` + its chunked-prefill carry events): v is monotone in
        n2, so certifying the bucket's worst case covers the actual grown
        context conservatively, and the knee test is evaluated once per
        (bucket, resumption_count) per process — not once per monitor
        tick."""
        with span(self.tracer, "serve.monitor"):
            running = [r for r, s in self.active.items() if not s.in_prefill]
            if not running:
                return
            sid = max(running, key=lambda r: self.pool.seq_len(r))
            ctx = self.pool.seq_len(sid)
            bucket_i, bucket = self.plan.bucket_for(ctx)
            width = bucket.max_pages(self.page_size)
            self._key, sub = jax.random.split(self._key)
            stats = self.executor.measure_vrr(
                self.pool.page_table([sid], width)[0], ctx, bucket.acc, sub)
            n2 = -(-ctx // self.page_size)
            swamp = float(stats.swamp_rate)
            v_pred = certified_log_v(
                bucket.m_acc, self.plan.m_p, self.page_size, bucket.max_ctx,
                extra_carry_events(self.page_size, self.plan.prefill_chunk,
                                   bucket.resumptions))
            breach_m = swamp >= self.swamp_threshold
            breach_p = v_pred >= CUTOFF_LOG_V
            breach = breach_m or breach_p
            if breach:
                self.plan = self.plan.bumped(bucket_i)
            # the realized width after the (carrier-clamped) bump — at the
            # m_acc ceiling a breach is a saturated no-op, and the log says so
            m_now = self.plan.buckets[bucket_i].m_acc
            event = {
                "step": self._decode_steps,
                "event": ("rebucket" if breach and m_now > bucket.m_acc
                          else "saturated" if breach else "ok"),
                "source": ("both" if breach_m and breach_p
                           else "measured" if breach_m
                           else "predicted" if breach_p else None),
                "gemm": "attn_decode", "role": "serve",
                "bucket": bucket_i, "ctx": ctx, "n1": self.page_size, "n2": n2,
                "m_acc": m_now,
                "measured_vrr": round(float(stats.measured_vrr), 6),
                "log_v": round(float(stats.measured_log_v(n2)), 4),
                "log_v_pred": round(float(v_pred), 4),
                "cutoff": round(CUTOFF_LOG_V, 4),
                "swamp_rate": round(swamp, 6),
                "swamp_threshold": self.swamp_threshold,
                # measured KV-magnitude hint from this window: what a re-plan
                # could certify the e_acc overflow bound with, vs the hint the
                # current plan was built under
                "v_hint_plan": self.plan.v_hint,
                "v_hint_measured": derive_v_hint(stats, ctx),
            }
            self.events.append(event)
            if self.monitor_log:
                jsonl_append(self.monitor_log, [event])
            if self.metrics is not None:
                from repro.obs.metrics import record_controller_events
                record_controller_events(self.metrics, [event],
                                         area="serve_monitor")

    # ------------------------------ accounting -----------------------------
    def utilization(self) -> float:
        """Decoded tokens per decode-batch slot: 1.0 = every step decoded a
        full batch.  The serve bench gates the chunked+preemptive engine's
        utilization against the reservation baseline on this number."""
        return self.decoded_tokens / max(self.steps * self.max_batch, 1)

    def kv_bytes_per_token(self, *, carrier_bytes: int = 1,
                           per_shard: bool = False) -> float:
        """Arena bytes per cached token: the GLOBAL logical footprint by
        default (unchanged by sharding — it is the same arena, split), or
        what ONE shard actually holds with ``per_shard=True`` (kv heads
        split ``tp_shards`` ways, page scale exponents replicated)."""
        return kv_bytes_per_token(
            self.pc, carrier_bytes=carrier_bytes,
            tp_shards=self.tp_shards if per_shard else 1)


def sharded_divergence(model, params, prompts, plan: AttnPlan, shards: int,
                       *, gen: int, n_pages: int, kv_fmt: FPFormat,
                       max_batch: int) -> str | None:
    """The sharded serving contract, checked: serve ``prompts`` (``gen``
    tokens each) on one device and on a ``shards``-way ``model`` mesh with
    the same weights and ``plan``, then decode one more step of the first
    prompt in each arena.  None when the token streams are equal and the
    arenas and that step's logits are bitwise equal, else what diverged."""
    page = plan.page_size
    pc = PagedKVConfig.for_model(model.cfg, n_pages=n_pages, page_size=page,
                                 kv_fmt=kv_fmt)
    n = len(prompts[0])
    b = plan.buckets[-1]
    row = np.zeros((1, b.max_pages(page)), np.int32)
    used = -(-(n + 1) // page)
    row[0, :used] = np.arange(1, used + 1)
    step = DecodeRequest(rids=(0,), last_tokens=(int(prompts[0][-1]),),
                         page_table=row, positions=(n,), seq_lens=(n + 1,),
                         acc=b.acc)

    def drive(executor):
        eng = ServeEngine(model, params, n_pages=n_pages, page_size=page,
                          max_batch=max_batch, executor=executor, plan=plan,
                          prefill_chunk_tokens=plan.prefill_chunk)
        rids = [eng.submit(list(p), gen) for p in prompts]
        out = eng.run()
        arena = {k: np.asarray(v) for k, v in eng.kv.items()}
        logits = np.asarray(executor.decode_logits(step))[0, 0]
        return [out[r] for r in rids], arena, logits

    out1, kv1, l1 = drive(ModelExecutor(model, params, pc, kv_fmt=kv_fmt,
                                        max_batch=max_batch))
    out_n, kv_n, l_n = drive(ShardedModelExecutor(
        model, params, pc, kv_fmt=kv_fmt, n_shards=shards,
        max_batch=max_batch))
    if out1 != out_n:
        return f"token streams {out1} vs {out_n}"
    bad = [k for k in kv1 if not np.array_equal(kv1[k], kv_n[k])]
    if bad:
        return f"arena {bad}"
    if not np.array_equal(l1, l_n):
        d = np.abs(l1.astype(np.float32) - l_n.astype(np.float32))
        return (f"decode logits: {int((d > 0).sum())} of {d.size} differ, "
                f"max |diff| {float(d.max())}")
    return None
