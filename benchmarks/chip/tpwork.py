"""Work and trace reductions of the tensor-parallel serving cells: each
chip's share of the kernel calls (every projection and the untied head
split by output columns, attention by heads) and the collective ops of
the device trace.  The count of shards is the configuration's
``program.shards``."""

from __future__ import annotations

import re

import kernels as K
import work as W
from trace_reduce import CONTAINERS, base_name, clip, union

# the collectives XLA emits, async halves (``-start``/``-done``) included:
# by an op's base name, or by the opcode its HLO text names, since XLA
# names some after the JAX primitive they came from
# (``%pmax.43 = f32[32,32]{...} all-reduce(...)``)
_KINDS = r"(?:all-gather|all-reduce|collective-permute|reduce-scatter|" \
    r"all-to-all)"
COLLECTIVE_NAME = re.compile(r"^" + _KINDS)
COLLECTIVE_OP = re.compile(r" " + _KINDS + r"(?:-start|-done)?\(")


def shards(cfg: dict) -> int:
    return int(cfg["program"]["shards"])


def layer_gemms(cfg: dict, m: int) -> list[tuple[int, int, int]]:
    """(M, K, N) of one chip's projections of a layer on ``m`` rows."""
    s = shards(cfg)
    return [(mm, k, n // s) for mm, k, n in K.layer_gemms(cfg, m)]


def forward_gemms(cfg: dict, m: int, head_rows: int | None) -> list:
    d, *_, v, n = K._dims(cfg)
    calls = layer_gemms(cfg, m) * n
    if head_rows:
        tied = bool(cfg.get("tie_word_embeddings"))
        calls.append((head_rows, d, v if tied else v // shards(cfg)))
    return calls


def gemm_calls(ctx) -> list:
    """One chip's GEMMs of every traced executor call, at the padded
    shapes the kernels are called with."""
    cfg, c = ctx["config"], ctx["counters"]
    calls = []
    for _ in c.get("decodes", []):
        calls += forward_gemms(cfg, c["max_batch"], c["max_batch"])
    for _, _, _, width, final in c.get("prefills", []):
        calls += forward_gemms(cfg, width, 1 if final else None)
    return calls


def decode_attention(ctx) -> tuple[W.Work, int]:
    """One chip's paged decode attention over the traced decode calls (its
    heads and KV heads), and the kernel calls: one a layer a call."""
    cfg, c = ctx["config"], ctx["counters"]
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    s, n = shards(cfg), cfg["num_hidden_layers"]
    total = W.ZERO
    for _, rows, lens in c.get("decodes", []):
        total += W.decode_attention(
            lens[:rows], heads=h // s,
            kv_heads=cfg["num_key_value_heads"] // s,
            head_dim=cfg.get("head_dim") or d // h,
            page_size=c["page_size"]).scaled(n)
    return total, len(c.get("decodes", [])) * n


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE_NAME.match(base_name(name))
                or COLLECTIVE_OP.search(name))


def collective_split(events, window) -> dict:
    """ns of the window on one chip: ``collective`` (the union of its
    collective ops), ``exposed`` (the part of that in which no other op
    runs; a scan's ``while`` spans its body and is no op of its own) and
    ``ops`` (the count of collective ops)."""
    ev = clip(events, window)
    coll = union([e for e in ev if is_collective(e[0])])
    other = union([e for e in ev if not is_collective(e[0])
                   and base_name(e[0]) not in CONTAINERS])
    exposed, j = 0, 0
    for s, e in coll:
        covered = 0
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            covered += min(e, other[k][1]) - max(s, other[k][0])
            k += 1
        exposed += e - s - covered
    return {"collective": sum(e - s for s, e in coll), "exposed": exposed,
            "ops": sum(1 for e in ev if is_collective(e[0]))}
