"""Kernel registry + block-size autotuner with a persistent JSON tuning table.

The Pallas GEMMs are parameterized by an MXU block decomposition
(block_m, block_n, block_k).  Only block_m / block_n are free perf knobs;
block_k is *numerics*: for a narrow accumulator it IS the paper's chunk
length n1 (the carry is rounded once per n1 products; the kernel loads K
tiles of ``chunk_tile(n1)`` and folds their chunks in order), and even for
the wide degenerate path it fixes the f32 partial-sum grouping — so the
tuner pins it (to the policy's chunk, or 128 for wide) and results never
depend on what is in the tuning table.

Components:

* a **kernel registry** — kernels self-register by name at import time
  (``@register_kernel("qmatmul_fused")``) so benchmarks/tools can enumerate
  and fetch them without hard-coding imports;
* ``candidate_blocks`` — MXU-aligned (block_m, block_n, block_k) triples
  constrained by the VMEM working-set budget (A-tile + B-tile + output tile
  + f32 carry scratch, plus the quantized-operand tiles when the fused
  kernel emits residuals) and by the chunk length as above;
* ``time_kernel`` — the wall-clock harness (compile once, then average over
  reps); ``benchmarks/kernel_bench.py`` uses this same function so tuner
  decisions and reported numbers come from one measurement path;
* ``TuningTable`` — a JSON file mapping a problem key (shape + chunk +
  accumulator/representation formats + per-operand quantization + residual
  emission/packing + operand dtype + the VMEM ceiling of the target TPU
  generation) to the winning blocks; ``blocks_for`` / ``pair_blocks_for``
  are the trace-time consults used by ``repro.kernels.ops.qdot`` (shape
  tuples are static under jit, so the lookup is pure Python at trace time
  and free at run time).

On the CPU the timings run in Pallas interpret mode — a proxy that ranks
by work per block decomposition, not TPU silicon truth.  The table format
is the contract; re-tuning on real hardware just rewrites the JSON.
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time
from typing import Any, Callable

import jax

from repro.runtime import CHECKOUT

__all__ = [
    "register_kernel",
    "get_kernel",
    "registered_kernels",
    "VMEM_PER_GENERATION",
    "DEVICE_KINDS",
    "device_generation",
    "vmem_budget",
    "vmem_block_bytes",
    "candidate_blocks",
    "time_kernel",
    "TuningTable",
    "get_table",
    "set_table_path",
    "blocks_for",
    "pair_blocks_for",
    "attn_blocks_for",
    "fmt_tuple",
    "operand_dtype",
    "autotune_qmatmul",
    "autotune_bwd_pair",
    "autotune_flash_prefill",
    "attn_vmem_bytes",
    "AttnCall",
]

# --------------------------------------------------------------------------
# kernel registry
# --------------------------------------------------------------------------

_REGISTRY: dict[str, Callable] = {}


def register_kernel(name: str):
    """Decorator: publish a kernel callable under ``name``."""

    def deco(fn: Callable) -> Callable:
        _REGISTRY[name] = fn
        return fn

    return deco


def get_kernel(name: str) -> Callable:
    import repro.kernels  # noqa: F401  (importing the package populates the registry)

    if name not in _REGISTRY:
        raise KeyError(f"unknown kernel {name!r}; registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def registered_kernels() -> dict[str, Callable]:
    import repro.kernels  # noqa: F401

    return dict(_REGISTRY)


# --------------------------------------------------------------------------
# candidate enumeration
# --------------------------------------------------------------------------

# VMEM per core by TPU generation.  The tuning budget is HALF of it — the
# other half is left for Pallas's double-buffered pipeline.  Tables tuned
# under different ceilings never share entries (the ceiling is in the key),
# so a table produced on a v4 host cannot hand a v6e-sized working set to a
# v4 core after the fleet mixes generations.
VMEM_PER_GENERATION = {
    "v4": 16 * 2**20,
    "v5e": 16 * 2**20,
    "v5p": 16 * 2**20,
    "v6e": 32 * 2**20,
}

# ``device_kind`` as JAX reports it -> generation
DEVICE_KINDS = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
    "TPU v6e": "v6e",
}

# what a run off the TPU (the CPU interpreter) plans its tiles for: the
# chip this repository is measured on
HOST_GENERATION = "v5e"


def device_generation(device=None) -> str:
    """The TPU generation of ``device`` (default: the first device), from
    its ``device_kind``.  Off the TPU it is ``HOST_GENERATION``; on a TPU
    whose kind is not known here it is an error, never a guess."""
    device = device or jax.devices()[0]
    if device.platform != "tpu":
        return HOST_GENERATION
    gen = DEVICE_KINDS.get(device.device_kind)
    if gen is None:
        raise ValueError(
            f"unknown TPU device_kind {device.device_kind!r}; add it to "
            f"repro.kernels.autotune.DEVICE_KINDS (known: {sorted(DEVICE_KINDS)})")
    return gen


def vmem_budget(generation: str | None = None) -> int:
    """The VMEM working-set budget for one grid step: half the generation's
    VMEM (default: the generation of the device this process runs on), or
    REPRO_VMEM_BUDGET verbatim when set."""
    env = os.environ.get("REPRO_VMEM_BUDGET")
    if env:
        return int(env)
    return VMEM_PER_GENERATION[generation or device_generation()] // 2


# alias for functions whose keyword argument shadows the name
_vmem_budget = vmem_budget

# MXU-aligned tile edges the tuner considers (lane width 128 and multiples).
_TILE_EDGES = (128, 256, 512)


def vmem_block_bytes(block_m: int, block_n: int, block_k: int,
                     *, emit_quantized: bool = False,
                     operand_bytes: int = 4,
                     residual_bytes: int = 4) -> int:
    """VMEM working set of one fused-GEMM grid step: A + B + out tiles plus
    the carry scratch (same shape as out); with ``emit_quantized`` the
    quantized-operand output tiles are also resident.  ``operand_bytes`` /
    ``residual_bytes`` price int8-packed carriers (1 byte) vs f32 (4)."""
    b = operand_bytes * (block_m * block_k + block_k * block_n)
    b += 4 * 2 * block_m * block_n
    if emit_quantized:
        b += residual_bytes * (block_m * block_k + block_k * block_n)
    return b


def candidate_blocks(m: int, k: int, n: int, *, chunk: int = 0,
                     emit_quantized: bool = False,
                     operand_bytes: int = 4,
                     residual_bytes: int = 4,
                     vmem_budget: int | None = None) -> list[tuple[int, int, int]]:
    """MXU-aligned (block_m, block_n, block_k) candidates for an M*K*N GEMM.

    block_k is always pinned, never swept: for a narrow accumulator it is
    the rounding cadence n1 (``chunk``; moving it changes the *result*), and
    for wide accumulation it still fixes the f32 partial-sum grouping, so
    pinning it at 128 keeps results reproducible across machines with
    different tuning tables.  Only block_m / block_n — provably
    schedule-only (the per-output-element reduction order over K is
    untouched) — are tuned.

    ``vmem_budget=None`` resolves the generation ceiling at call time.
    """
    if vmem_budget is None:
        vmem_budget = _vmem_budget()

    def edges(dim: int) -> list[int]:
        padded = max(-(-dim // 128) * 128, 128)
        return [t for t in _TILE_EDGES if t <= padded] or [128]

    from repro.kernels.common import chunk_tile

    bk = chunk if chunk > 0 else 128
    tile_k = chunk_tile(bk)  # the K tile the kernel loads for this chunk
    out = [
        (bm, bn, bk)
        for bm in edges(m)
        for bn in edges(n)
        if vmem_block_bytes(bm, bn, tile_k, emit_quantized=emit_quantized,
                            operand_bytes=operand_bytes,
                            residual_bytes=residual_bytes) <= vmem_budget
    ]
    return out or [(128, 128, bk)]


# --------------------------------------------------------------------------
# timing harness (shared with benchmarks/kernel_bench.py)
# --------------------------------------------------------------------------


def time_kernel(fn: Callable, *args, reps: int = 3) -> float:
    """Mean wall-time of ``fn(*args)`` in microseconds, after one warm-up
    call that absorbs compilation."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


# --------------------------------------------------------------------------
# tuning table
# --------------------------------------------------------------------------

DEFAULT_TABLE_PATH = os.environ.get(
    "REPRO_AUTOTUNE_TABLE", os.path.join(CHECKOUT, ".autotune", "table.json"))


def operand_dtype(a_packed: bool = False, b_packed: bool = False) -> str:
    """Canonical operand-dtype key string: "f32" when both operands are f32
    carriers, else the per-operand pair (e.g. "f32i8" = f32 A, packed-int8
    B).  The single normalization shared by the tuner and qdot's trace-time
    consult, so keys cannot drift between the two."""
    if not a_packed and not b_packed:
        return "f32"
    return ("i8" if a_packed else "f32") + ("i8" if b_packed else "f32")


def fmt_tuple(repr_fmt) -> tuple[int, int] | None:
    """Normalize an FPFormat / (e, m) tuple / None to a plain tuple — the
    single normalization used by table keys, the warmup, and qdot."""
    if repr_fmt is None:
        return None
    if isinstance(repr_fmt, tuple):
        return (int(repr_fmt[0]), int(repr_fmt[1]))
    return (int(repr_fmt.e), int(repr_fmt.m))


def _table_key(m: int, k: int, n: int, chunk: int, e_acc: int, m_acc: int,
               repr_fmt, emit_quantized: bool,
               quantize_a: bool, quantize_b: bool,
               dtype: str = "f32", vmem: int | None = None,
               pack_residuals: bool = False) -> str:
    """Problem key: shape AND the full kernel configuration — accumulator
    format, representation format, per-operand quantization/packing, residual
    emission, operand dtype, and the VMEM ceiling the candidates were
    enumerated under — so differently configured GEMMs over the same shape
    (or the same GEMM tuned for a different TPU generation) never share an
    entry.  The output epilogue (out_fmt) is deliberately NOT keyed:
    epilogue quantization is schedule-neutral VPU work."""
    r = fmt_tuple(repr_fmt)
    if r is None:
        # no representation format: the quantize flags are inert — fold
        # them to the canonical value so equivalent kernels share one entry
        quantize_a = quantize_b = True
    rs = "none" if r is None else f"{r[0]}.{r[1]}"
    vm = vmem if vmem is not None else vmem_budget()
    emit = 2 if (emit_quantized and pack_residuals) else int(bool(emit_quantized))
    return (f"{m}x{k}x{n}:c{chunk}:acc{e_acc}.{m_acc}:r{rs}"
            f":qa{int(bool(quantize_a))}qb{int(bool(quantize_b))}"
            f":e{emit}:d{dtype}:v{vm >> 20}")


def _pair_key(t: int, k: int, n: int, bwd_chunk: int, grad_chunk: int,
              bwd_acc: tuple[int, int], grad_acc: tuple[int, int],
              repr_fmt, packed: bool, dtype: str = "f32",
              vmem: int | None = None) -> str:
    """Problem key for the fused backward-pair kernel (dx+dw in one pass):
    shape, both chunk lengths, both accumulator formats, the representation
    format, the residual carrier (packed int8 vs f32), operand dtype and
    the VMEM ceiling."""
    r = fmt_tuple(repr_fmt)
    rs = "none" if r is None else f"{r[0]}.{r[1]}"
    vm = vmem if vmem is not None else vmem_budget()
    return (f"pair:{t}x{k}x{n}:cb{bwd_chunk}.cg{grad_chunk}"
            f":accb{bwd_acc[0]}.{bwd_acc[1]}.accg{grad_acc[0]}.{grad_acc[1]}"
            f":r{rs}:p{int(bool(packed))}:d{dtype}:v{vm >> 20}")


def attn_vmem_bytes(block_q: int, chunk: int, dh: int,
                    *, kv_bytes: int = 4) -> int:
    """VMEM working set of one flash-attention grid step: q/out tiles, the
    K/V block, the o-carry scratch and the (block_q, 1) max/l rows.
    ``kv_bytes`` prices the K/V block carrier — 1 for int8 pages unpacked
    in VMEM, 4 for f32 carrier pages."""
    return (4 * block_q * dh            # q tile
            + 2 * kv_bytes * chunk * dh  # k + v block
            + 4 * block_q * dh           # out tile
            + 4 * block_q * dh           # o carry scratch
            + 2 * 4 * block_q)           # running max + l carry


def _attn_key(s: int, h: int, dh: int, chunk: int, e_acc: int, m_acc: int,
              kv_fmt, dtype: str = "f32", vmem: int | None = None) -> str:
    """Problem key for the serve-path attention kernels — same shape as the
    GEMM keys (geometry + chunk + accumulator format + KV representation
    format + operand dtype + VMEM ceiling) so attention entries live in the
    same table under the same drift rules."""
    r = fmt_tuple(kv_fmt)
    rs = "none" if r is None else f"{r[0]}.{r[1]}"
    vm = vmem if vmem is not None else vmem_budget()
    return (f"attn:{s}x{h}x{dh}:c{chunk}:acc{e_acc}.{m_acc}:r{rs}"
            f":d{dtype}:v{vm >> 20}")


@dataclasses.dataclass(frozen=True)
class AttnCall:
    """One serve-path attention invocation, fully specified.

    The bucket key (``table_key``), the jit-static compiled signature
    (``static_signature``) and the knee-certified accumulator format
    (``acc``) are all derived from this one struct, so the autotuner, the
    executor's compile cache and the planner cannot drift apart — the old
    arrangement kept three hand-maintained tuples in sync.

    It describes a call of the bucketed paged prefill kernel
    (``flash_prefill_paged``): geometry scalars ride in as traced
    scalar-prefetch operands and the page row is padded to ``max_pages``
    (required, > 0), so every slab of every prompt in the bucket shares one
    compiled kernel.
    """

    s: int                    # query tokens per call (slab width, padded)
    h: int                    # query heads
    dh: int                   # head dim
    chunk: int                # carry rounding cadence (== KV page size)
    max_pages: int            # padded page-row width of the bucket
    e_acc: int = 8
    m_acc: int = 23
    kv_fmt: Any = None        # packed KV representation format, or None
    kv_heads: int = 0         # KV heads; 0 means h (no GQA)
    block_q: int = 0          # explicit override; 0 = consult the table
    return_carry: bool = False
    dtype: str = "f32"

    def __post_init__(self):
        if self.max_pages <= 0:
            raise ValueError(f"max_pages must be > 0, got {self.max_pages}")
        object.__setattr__(self, "kv_fmt", fmt_tuple(self.kv_fmt))

    @property
    def acc(self) -> tuple[int, int]:
        return (self.e_acc, self.m_acc)

    def table_key(self, vmem: int | None = None) -> str:
        """Tuning-table key: the attention key plus ``:p{max_pages}``."""
        key = _attn_key(self.s, self.h, self.dh, self.chunk, self.e_acc,
                        self.m_acc, self.kv_fmt, dtype=self.dtype, vmem=vmem)
        return f"{key}:p{self.max_pages}"

    def resolve_block_q(self, vmem: int | None = None) -> int:
        """block_q is the only schedule-only knob: explicit override, else
        the tuned entry, else the safe default 128."""
        if self.block_q:
            return int(self.block_q)
        e = get_table().get_key(self.table_key(vmem=vmem))
        return int(e["block_q"]) if e is not None else 128

    def static_signature(self) -> tuple:
        """Everything jit-static about the compiled call — two AttnCalls
        with equal signatures hit the same XLA executable."""
        return (self.s, self.h, self.dh, self.chunk, self.e_acc, self.m_acc,
                self.kv_fmt, self.kv_heads, self.max_pages,
                self.resolve_block_q(), self.return_carry, self.dtype)


class TuningTable:
    """JSON-backed map from GEMM problem key to the winning block triple.

    Entries: ``{"block_m", "block_n", "block_k", "us", "candidates"}``.
    ``save`` re-reads the file and merges before the atomic tmp+rename
    write, so concurrent tuners neither tear the file nor drop each
    other's entries (last writer wins only on identical keys); reads are
    cached in memory for the trace-time fast path.
    """

    def __init__(self, path: str | None = None):
        self.path = path or DEFAULT_TABLE_PATH
        self._entries: dict[str, dict] | None = None

    def entries(self) -> dict[str, dict]:
        if self._entries is None:
            try:
                with open(self.path) as f:
                    self._entries = json.load(f)
            except (FileNotFoundError, json.JSONDecodeError):
                self._entries = {}
        return self._entries

    def get_key(self, key: str) -> dict | None:
        return self.entries().get(key)

    def put_key(self, key: str, entry: dict, *, persist: bool = True) -> None:
        self.entries()[key] = entry
        if persist:
            self.save()

    def get(self, m: int, k: int, n: int, chunk: int, *, e_acc: int = 8,
            m_acc: int = 23, repr_fmt=None, emit_quantized: bool = False,
            quantize_a: bool = True, quantize_b: bool = True,
            dtype: str = "f32", vmem: int | None = None,
            pack_residuals: bool = False) -> dict | None:
        return self.get_key(
            _table_key(m, k, n, chunk, e_acc, m_acc, repr_fmt,
                       emit_quantized, quantize_a, quantize_b,
                       dtype=dtype, vmem=vmem, pack_residuals=pack_residuals))

    def put(self, m: int, k: int, n: int, chunk: int, entry: dict, *,
            e_acc: int = 8, m_acc: int = 23, repr_fmt=None,
            emit_quantized: bool = False, quantize_a: bool = True,
            quantize_b: bool = True, dtype: str = "f32",
            vmem: int | None = None, pack_residuals: bool = False,
            persist: bool = True) -> None:
        key = _table_key(m, k, n, chunk, e_acc, m_acc, repr_fmt,
                         emit_quantized, quantize_a, quantize_b,
                         dtype=dtype, vmem=vmem, pack_residuals=pack_residuals)
        self.put_key(key, entry, persist=persist)

    def save(self) -> None:
        # merge-on-save: pick up entries another process tuned since we
        # last read, preferring our own on key collisions
        try:
            with open(self.path) as f:
                merged = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            merged = {}
        merged.update(self.entries())
        self._entries = merged
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)


_TABLE: TuningTable | None = None


def get_table() -> TuningTable:
    global _TABLE
    if _TABLE is None:
        _TABLE = TuningTable()
    return _TABLE


def set_table_path(path: str | None) -> TuningTable:
    """Point the process-global table at ``path`` (tests, custom caches)."""
    global _TABLE
    _TABLE = TuningTable(path)
    return _TABLE


def blocks_for(m: int, k: int, n: int, chunk: int = 0, *, e_acc: int = 8,
               m_acc: int = 23, repr_fmt=None, emit_quantized: bool = False,
               quantize_a: bool = True, quantize_b: bool = True,
               dtype: str = "f32", vmem: int | None = None,
               pack_residuals: bool = False) -> tuple[int, int, int]:
    """Trace-time consult: tuned blocks for this GEMM configuration, or the
    safe default (128, 128, chunk-or-128) when it has not been tuned.

    block_k is ALWAYS the pinned cadence (chunk, or 128 for wide) — never
    taken from the table — so qdot numerics cannot depend on tuning state.
    """
    bk = chunk if chunk > 0 else 128
    e = get_table().get(m, k, n, chunk, e_acc=e_acc, m_acc=m_acc,
                        repr_fmt=repr_fmt, emit_quantized=emit_quantized,
                        quantize_a=quantize_a, quantize_b=quantize_b,
                        dtype=dtype, vmem=vmem, pack_residuals=pack_residuals)
    if e is not None:
        return (int(e["block_m"]), int(e["block_n"]), bk)
    return (128, 128, bk)


def pair_blocks_for(t: int, k: int, n: int, *, bwd_chunk: int = 0,
                    grad_chunk: int = 0, bwd_acc=(8, 23), grad_acc=(8, 23),
                    repr_fmt=None, packed: bool = True, dtype: str = "f32",
                    vmem: int | None = None) -> tuple[int, int, int]:
    """Trace-time consult for the backward-pair kernel: (block_t, block_k,
    block_n).  block_t / block_n are the two rounding cadences (grad / bwd
    chunks — numerics, pinned); only block_k comes from the table."""
    bt = grad_chunk if grad_chunk > 0 else 128
    bn = bwd_chunk if bwd_chunk > 0 else 128
    e = get_table().get_key(_pair_key(
        t, k, n, bn, bt, tuple(bwd_acc), tuple(grad_acc), repr_fmt,
        packed, dtype=dtype, vmem=vmem))
    bk = int(e["block_k"]) if e is not None else 128
    return (bt, bk, bn)


def attn_blocks_for(s: int, h: int, dh: int, chunk: int, *, max_pages: int,
                    e_acc: int = 8, m_acc: int = 23, kv_fmt=None,
                    dtype: str = "f32", vmem: int | None = None) -> int:
    """Trace-time consult for the prefill kernel's block_q (the only
    schedule-only knob: ``chunk`` is the carry rounding cadence — numerics,
    pinned to the KV page size by the serve path — and the decode kernel's
    grid is fixed by the page geometry outright)."""
    return AttnCall(s, h, dh, chunk, max_pages, e_acc=e_acc, m_acc=m_acc,
                    kv_fmt=kv_fmt, dtype=dtype).resolve_block_q(vmem)


# --------------------------------------------------------------------------
# the tuner
# --------------------------------------------------------------------------


def _rand_operand(key, shape, packed: bool, repr_fmt):
    """Random f32 timing data; packed operands are materialized as the int8
    codes the timed kernel actually DMAs."""
    import jax.numpy as jnp

    x = jax.random.normal(key, shape, jnp.float32)
    if not packed:
        return x
    if repr_fmt is None:
        raise ValueError("packed operands need repr_fmt to encode")
    from repro.quant.formats import FPFormat
    from repro.quant.qtensor import QTensor

    return QTensor.pack(x, FPFormat(e=repr_fmt[0], m=repr_fmt[1])).payload


def autotune_qmatmul(
    m: int,
    k: int,
    n: int,
    *,
    chunk: int = 0,
    e_acc: int = 8,
    m_acc: int = 23,
    repr_fmt: Any = None,
    emit_quantized: bool = False,
    quantize_a: bool = True,
    quantize_b: bool = True,
    a_packed: bool = False,
    b_packed: bool = False,
    pack_residuals: bool = False,
    dtype: str | None = None,
    vmem: int | None = None,
    reps: int = 2,
    seed: int = 0,
    table: TuningTable | None = None,
    persist: bool = True,
    verbose: bool = False,
) -> dict:
    """Time every admissible block decomposition of the fused GEMM on random
    data and record the winner in the tuning table.

    Returns the table entry.  Re-tuning an already-tuned shape overwrites it
    (the table is a cache, not an append log).  The operand dtype ("i8" for
    packed residual inputs) and the VMEM ceiling are part of the key.
    """
    from repro.kernels.fused import qmatmul_fused  # late: avoid import cycle

    repr_fmt = fmt_tuple(repr_fmt)
    dtype = dtype or operand_dtype(a_packed, b_packed)
    budget = vmem if vmem is not None else vmem_budget()
    cfg_key = dict(e_acc=e_acc, m_acc=m_acc, repr_fmt=repr_fmt,
                   emit_quantized=emit_quantized,
                   quantize_a=quantize_a, quantize_b=quantize_b,
                   dtype=dtype, vmem=budget, pack_residuals=pack_residuals)
    table = table or get_table()
    cached = table.get(m, k, n, chunk, **cfg_key)
    if cached is not None and cached.get("reps", 0) >= reps:
        return cached

    # NOTE: a non-default ``dtype`` (e.g. "bf16" for the MoE expert-einsum
    # shapes) labels the KEY only — the fused kernel itself computes on f32
    # carriers (pad2d casts on entry), so the timing is the same f32
    # interpret-mode proxy as every other entry.  The label reserves the
    # table slot the einsum path will consult if/when it routes through the
    # fused kernel; a silicon re-tune overwrites the numbers in place.
    key = jax.random.PRNGKey(seed)
    ka, kb = jax.random.split(key)
    a = _rand_operand(ka, (m, k), a_packed, repr_fmt)
    b = _rand_operand(kb, (k, n), b_packed, repr_fmt)

    cands = candidate_blocks(
        m, k, n, chunk=chunk, emit_quantized=emit_quantized,
        operand_bytes=1 if (a_packed and b_packed) else 4,
        residual_bytes=1 if pack_residuals else 4,
        vmem_budget=budget)
    best: tuple[float, tuple[int, int, int]] | None = None
    for bm, bn, bk in cands:
        def run(a, b, _bm=bm, _bn=bn, _bk=bk):
            return qmatmul_fused(
                a, b, repr_fmt=repr_fmt, e_acc=e_acc, m_acc=m_acc,
                block_m=_bm, block_n=_bn, block_k=_bk,
                quantize_a=quantize_a, quantize_b=quantize_b,
                a_packed=a_packed, b_packed=b_packed,
                return_quantized=emit_quantized,
                pack_residuals=pack_residuals,
            )

        us = time_kernel(run, a, b, reps=reps)
        if verbose:
            print(f"  autotune {m}x{k}x{n} c{chunk}: "
                  f"({bm},{bn},{bk}) -> {us:.0f}us")
        if best is None or us < best[0]:
            best = (us, (bm, bn, bk))

    us, (bm, bn, bk) = best
    entry = {
        "block_m": bm, "block_n": bn, "block_k": bk,
        "us": round(us, 1), "candidates": len(cands), "reps": reps,
    }
    table.put(m, k, n, chunk, entry, persist=persist, **cfg_key)
    return entry


def autotune_bwd_pair(
    t: int,
    k: int,
    n: int,
    *,
    bwd_chunk: int = 0,
    grad_chunk: int = 0,
    bwd_acc: tuple[int, int] = (8, 23),
    grad_acc: tuple[int, int] = (8, 23),
    repr_fmt: Any = None,
    packed: bool = True,
    dtype: str | None = None,
    vmem: int | None = None,
    reps: int = 2,
    seed: int = 0,
    table: TuningTable | None = None,
    persist: bool = True,
    verbose: bool = False,
) -> dict:
    """Tune block_k of the fused backward-pair kernel (block_t / block_n are
    the two rounding cadences — numerics, never swept).  ``dtype`` labels
    the key like the GEMM tuner's (e.g. "bf16" for the MoE expert shapes
    routed through qdot with ``table_dtype``)."""
    import jax.numpy as jnp

    from repro.kernels.bwd_pair import pair_vmem_bytes, qmatmul_bwd_pair

    repr_fmt = fmt_tuple(repr_fmt)
    if packed and repr_fmt is None:
        raise ValueError("packed residuals need repr_fmt to decode "
                         "(pass repr_fmt, or packed=False for f32 carriers)")
    budget = vmem if vmem is not None else vmem_budget()
    bt = grad_chunk if grad_chunk > 0 else 128
    bn = bwd_chunk if bwd_chunk > 0 else 128
    key_str = _pair_key(t, k, n, bn, bt, tuple(bwd_acc), tuple(grad_acc),
                        repr_fmt, packed, dtype=dtype or "f32", vmem=budget)
    table = table or get_table()
    cached = table.get_key(key_str)
    if cached is not None and cached.get("reps", 0) >= reps:
        return cached

    rk = jax.random.PRNGKey(seed)
    kg, kx, kw = jax.random.split(rk, 3)
    g = jax.random.normal(kg, (t, n), jnp.float32)
    xq = _rand_operand(kx, (t, k), packed, repr_fmt)
    wq = _rand_operand(kw, (k, n), packed, repr_fmt)

    from repro.kernels.common import chunk_tile

    tile_t, tile_n = chunk_tile(bt), chunk_tile(bn)
    np_ = max(-(-n // tile_n) * tile_n, tile_n)
    cands = [bk for bk in _TILE_EDGES
             if bk <= max(-(-k // 128) * 128, 128)
             and pair_vmem_bytes(tile_t, bk, tile_n, np_,
                                 packed=packed) <= budget]
    cands = cands or [128]
    best: tuple[float, int] | None = None
    for bk in cands:
        def run(g, xq, wq, _bk=bk):
            return qmatmul_bwd_pair(
                g, xq, wq, repr_fmt=repr_fmt, bwd_acc=tuple(bwd_acc),
                grad_acc=tuple(grad_acc), block_t=bt, block_k=_bk,
                block_n=bn, packed=packed)

        us = time_kernel(run, g, xq, wq, reps=reps)
        if verbose:
            print(f"  autotune pair {t}x{k}x{n}: bk={bk} -> {us:.0f}us")
        if best is None or us < best[0]:
            best = (us, bk)

    us, bk = best
    entry = {"block_t": bt, "block_k": bk, "block_n": bn,
             "us": round(us, 1), "candidates": len(cands), "reps": reps}
    table.put_key(key_str, entry, persist=persist)
    return entry


def autotune_flash_prefill(
    call: AttnCall,
    *,
    vmem: int | None = None,
    reps: int = 2,
    seed: int = 0,
    table: TuningTable | None = None,
    persist: bool = True,
    verbose: bool = False,
) -> dict:
    """Tune the prefill kernel's block_q for one bucket's ``AttnCall`` (the
    same spec the executor compiles against; ``chunk`` is the carry cadence
    — numerics, never swept): time ``flash_prefill_paged`` over a dummy
    page arena and record the winner under the call's ``attn:…:p{max_pages}``
    key in the shared tuning table."""
    import jax.numpy as jnp

    from repro.kernels.attention import flash_prefill_paged  # late: cycle

    s, h, dh, chunk = call.s, call.h, call.dh, call.chunk
    budget = vmem if vmem is not None else vmem_budget()
    key_str = call.table_key(vmem=budget)
    table = table or get_table()
    cached = table.get_key(key_str)
    if cached is not None and cached.get("reps", 0) >= reps:
        return cached

    kv_bytes = 1 if call.kv_fmt is not None else 4
    sp = max(-(-s // 128) * 128, 128)
    cands = [bq for bq in _TILE_EDGES
             if bq <= sp and attn_vmem_bytes(bq, chunk, dh,
                                             kv_bytes=kv_bytes) <= budget]
    cands = cands or [128]

    rk = jax.random.PRNGKey(seed)
    kq, kk, kv_ = jax.random.split(rk, 3)
    q = jax.random.normal(kq, (s, h, dh), jnp.float32)
    kvh = call.kv_heads or h
    n_pg = call.max_pages
    if call.kv_fmt is not None:
        kp = jax.random.randint(kk, (n_pg, kvh, chunk, dh), -63, 64, jnp.int8)
        vp = jax.random.randint(kv_, (n_pg, kvh, chunk, dh), -63, 64,
                                jnp.int8)
    else:
        kp = jax.random.normal(kk, (n_pg, kvh, chunk, dh), jnp.float32)
        vp = jax.random.normal(kv_, (n_pg, kvh, chunk, dh), jnp.float32)
    se = jnp.zeros((n_pg,), jnp.int32)
    row = jnp.arange(n_pg, dtype=jnp.int32)
    kv_len = jnp.int32(min(s, n_pg * chunk))

    def make_run(bq):
        def run(q, kp, vp):
            c = dataclasses.replace(call, block_q=bq)
            return flash_prefill_paged(
                q, kp, vp, se, se, row, jnp.int32(0), jnp.int32(s),
                kv_len, call=c)
        return run

    best: tuple[float, int] | None = None
    for bq in cands:
        us = time_kernel(make_run(bq), q, kp, vp, reps=reps)
        if verbose:
            print(f"  autotune attn {s}x{h}x{dh} c{chunk}: bq={bq} -> {us:.0f}us")
        if best is None or us < best[0]:
            best = (us, bq)

    us, bq = best
    entry = {"block_q": bq, "us": round(us, 1),
             "candidates": len(cands), "reps": reps}
    table.put_key(key_str, entry, persist=persist)
    return entry
