"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract memory / cost / collective statistics.

MUST set the fake-device flag before any jax import (jax locks the device
count at first init) — hence the first two lines.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
      --shape train_4k [--multi-pod] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all   # every cell, one mesh
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
)
# NOTE on cost_analysis(): XLA counts a while-loop body ONCE, so this
# rolled, microbatched pass under-reports FLOPs/bytes by ~n_layers x
# microbatches.  It is the *memory/compile-validity* pass (production HLO).
# Exact per-step costs come from repro.launch.costrun (per-layer
# composition over small unrolled variants); benchmarks/roofline.py merges
# the two.  Set REPRO_UNROLL_SCANS=1 to force full unrolling here instead.

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ALIASES, get_config, shape_cells  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import input_specs  # noqa: E402
from repro.models.api import get_model  # noqa: E402
from repro.models.layers import Dist  # noqa: E402
from repro.sharding.specs import (  # noqa: E402
    ShardingRules,
    batch_spec,
    build_param_specs,
)
from repro.train.loop import TrainConfig, make_train_step  # noqa: E402
from repro.train.optimizer import OptConfig  # noqa: E402

def _prod(t):
    n = 1
    for d in t:
        n *= int(d)
    return n


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def make_dist(mesh) -> Dist:
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return Dist(mesh=mesh, data_axes=axes, model_axis="model")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _SHAPE_BYTES.get(dtype, 4)


_OP_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^)]*?\)?\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_TUPLE_OP_RE = re.compile(
    r"=\s+\(([^)]*)\)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_GROUP_RE = re.compile(r"replica_groups=\{?\{([0-9,]+)\}")
_GROUP_RE2 = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _group_size(line: str) -> int:
    m = _GROUP_RE2.search(line)
    if m:
        return int(m.group(2))
    m = _GROUP_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


def collective_bytes(hlo_text: str) -> dict:
    """Wire-bytes per collective type from post-SPMD optimized HLO.

    Ring model per op (size = result buffer bytes, n = group size):
      all-reduce        2 * size * (n-1)/n
      all-gather        size * (n-1)/n
      reduce-scatter    size * (n-1)        (input = n * result)
      all-to-all        size * (n-1)/n
      collective-permute size
    """
    out = {k: 0.0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    seen_done = set()
    for line in hlo_text.splitlines():
        kind = None
        sizes = []
        m = _OP_RE.search(line)
        if m:
            kind = m.group(3)
            sizes = [_shape_bytes(m.group(1), m.group(2))]
        else:
            m2 = _TUPLE_OP_RE.search(line)
            if m2:
                kind = m2.group(2)
                for part in m2.group(1).split("),"):
                    pm = re.match(r"\s*([a-z0-9]+)\[([0-9,]*)\]", part)
                    if pm:
                        sizes.append(_shape_bytes(pm.group(1), pm.group(2)))
        if kind is None:
            continue
        if "-done(" in line:
            continue  # paired with its -start; count once
        n = _group_size(line)
        size = float(sum(sizes))
        if kind == "all-reduce":
            wire = 2 * size * (n - 1) / n
        elif kind == "all-gather":
            wire = size * (n - 1) / n
        elif kind == "reduce-scatter":
            wire = size * (n - 1)
        elif kind == "all-to-all":
            wire = size * (n - 1) / n
        else:
            wire = size
        out[kind] += wire
        counts[kind] += 1
    out["total"] = sum(out[k] for k in COLLECTIVES)
    out["counts"] = counts
    return out


def _cost(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and (
                "flops" in k or "bytes accessed" in k or k in ("utilization",))}


def _memory(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = [k for k in dir(ma) if not k.startswith("_")]
    out = {}
    for k in keys:
        try:
            v = getattr(ma, k)
            if isinstance(v, int):
                out[k] = v
        except Exception:
            pass
    return out


def _zero1_specs(param_specs, shapes, mesh):
    """Optimizer-moment specs: additionally shard over 'pod' (ZeRO-1)."""
    if "pod" not in mesh.shape:
        return param_specs
    pod = mesh.shape["pod"]
    data = mesh.shape.get("data", 1)

    def upgrade(spec: P, shape):
        parts = list(spec) + [None] * (len(shape) - len(spec))
        for i, (axis, dim) in enumerate(zip(parts, shape)):
            if axis == "data" and dim % (pod * data) == 0:
                parts[i] = ("pod", "data")
                return P(*parts)
        for i, (axis, dim) in enumerate(zip(parts, shape)):
            if axis is None and dim % pod == 0:
                parts[i] = "pod"
                return P(*parts)
        return spec

    return jax.tree.map(
        lambda s, leaf: upgrade(s, tuple(leaf.shape)), param_specs, shapes,
        is_leaf=lambda x: isinstance(x, P))


def build_cell(arch: str, shape_name: str, mesh, *, microbatches: int = 4):
    """Returns (jitted_fn, abstract_args tuple) for one cell."""
    return build_cell_cfg(get_config(arch), shape_name, mesh,
                          microbatches=microbatches)


def build_cell_cfg(cfg, shape_name: str, mesh, *, microbatches: int = 4):
    """build_cell for an explicit ModelConfig (cost-composition variants)."""
    model = get_model(cfg)
    dist = make_dist(mesh)
    kind, specs = input_specs(cfg, shape_name, mesh)

    key_struct = jax.ShapeDtypeStruct((2,), jnp.uint32,
                                      sharding=NamedSharding(mesh, P()))
    param_shapes = jax.eval_shape(model.init_params, key_struct)

    # Inference is weight-stationary: FSDP-sharding params over 'data' makes
    # every decode step re-gather them (the dominant collective in the
    # baseline decode cells — EXPERIMENTS.md §Perf).  Replicate over 'data'
    # whenever the per-model-shard bf16 params fit comfortably; keep FSDP
    # for models that need it (llama4-maverick).
    fsdp = True
    if kind != "train":
        n_params = sum(
            int(_prod(l.shape)) for l in jax.tree.leaves(param_shapes))
        model_shards = mesh.shape.get("model", 1)
        fsdp = (2.0 * n_params / model_shards) > 8e9

    rules = ShardingRules(mesh, fsdp=fsdp)
    pspecs = build_param_specs(param_shapes, rules)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))

    if kind == "train":
        from repro.train.optimizer import LossScaleConfig

        shape = specs["batch"]["tokens"].shape
        microbatches = int(os.environ.get("REPRO_MICROBATCHES", microbatches))
        mb = microbatches if shape[0] % microbatches == 0 else 1
        tc = TrainConfig(opt=OptConfig(), microbatches=mb,
                         scaler=LossScaleConfig(dynamic=True))
        step = make_train_step(model, tc, dist)
        mspecs = _zero1_specs(pspecs, param_shapes, mesh)
        msh = jax.tree.map(lambda s: NamedSharding(mesh, s), mspecs,
                           is_leaf=lambda x: isinstance(x, P))
        rep = NamedSharding(mesh, P())
        state_sh = {
            "params": psh,
            "opt": {"m": msh, "v": msh, "step": rep},
            "scaler": {"scale": rep, "good_steps": rep},
        }
        state_struct = {
            "params": _with_sh(param_shapes, psh),
            "opt": {
                "m": _with_sh(_f32(param_shapes), msh),
                "v": _with_sh(_f32(param_shapes), msh),
                "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            },
            "scaler": {
                "scale": jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
                "good_steps": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            },
        }
        fn = jax.jit(step, donate_argnums=(0,))
        return fn, (state_struct, specs["batch"])

    # serving params: bf16 (production inference dtype)
    serve_params = _with_sh(_bf16(param_shapes), psh)
    if kind == "prefill":
        if cfg.family == "encdec":
            from repro.models import encdec

            fn = jax.jit(lambda p, b: encdec.prefill(p, b, cfg, dist))
        else:
            fn = jax.jit(lambda p, b: model.prefill(p, b, cfg, dist))
        return fn, (serve_params, specs["batch"])

    # decode
    if cfg.family == "encdec":
        from repro.models import encdec

        fn = jax.jit(lambda p, t, s, pos: encdec.decode_step(p, t, s, pos, cfg, dist))
    else:
        fn = jax.jit(lambda p, t, s, pos: model.decode_step(p, t, s, pos, cfg, dist))
    return fn, (serve_params, specs["tokens"], specs["state"], specs["pos"])


def _with_sh(shapes, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


def _f32(shapes):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), shapes)


def _bf16(shapes):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
        shapes)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             microbatches: int = 4, out_dir: str | None = None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    fn, args = build_cell(arch, shape_name, mesh, microbatches=microbatches)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    mem = _memory(compiled)
    cost = _cost(compiled)
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem,
        "cost": cost,
        "collectives": coll,
        "hlo_bytes": len(hlo),
    }
    print(f"== {arch} x {shape_name} [{rec['mesh']}] "
          f"lower={t_lower:.0f}s compile={t_compile:.0f}s")
    print("memory_analysis:", json.dumps(mem))
    print("cost_analysis:", json.dumps(cost))
    print("collective_bytes:", json.dumps({k: v for k, v in coll.items()}))
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch.replace('/', '_')}__{shape_name}__{rec['mesh'].replace('x', '_')}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    if args.all:
        for arch in ALIASES:
            for shape in shape_cells(arch):
                run_cell(arch, shape, multi_pod=args.multi_pod,
                         microbatches=args.microbatches, out_dir=args.out)
        return
    assert args.arch and args.shape
    run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
             microbatches=args.microbatches, out_dir=args.out)


if __name__ == "__main__":
    main()
