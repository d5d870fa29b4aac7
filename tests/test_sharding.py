"""Sharding rules (in-process, no devices needed) + distributed-parity
tests (subprocess with fake multi-device CPU)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.specs import batch_spec
from tests.conftest import run_child


class FakeMesh:
    """Duck-typed mesh exposing .shape (a dict) for rule unit-tests."""

    def __init__(self, **shape):
        self.shape = shape


def _specs_for(arch="qwen2-1.5b", **mesh_shape):
    # build specs against a fake mesh: rules only consult mesh.shape
    from repro.configs import get_smoke_config
    from repro.models.api import get_model
    from repro.sharding.specs import ShardingRules, build_param_specs

    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    shapes = jax.eval_shape(model.init_params,
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    rules = ShardingRules(FakeMesh(**mesh_shape))
    return build_param_specs(shapes, rules), cfg


def test_dense_param_specs():
    specs, cfg = _specs_for("qwen2-1.5b", data=2, model=2)
    lyr = specs["layers"]
    # column-parallel: last dim model, penultimate data (layer dim leading)
    assert lyr["attn"]["wq"] == P(None, "data", "model")
    assert lyr["attn"]["wk"] == P(None, "data", "model")
    # row-parallel: penultimate model, last data
    assert lyr["attn"]["wo"] == P(None, "model", "data")
    assert lyr["mlp"]["w_down"] == P(None, "model", "data")
    # embed: vocab -> model, d -> data
    assert specs["embed"] == P("model", "data")
    # norms replicated
    assert specs["final_norm"] == P(None)


def test_moe_param_specs_expert_parallel():
    specs, cfg = _specs_for("moonshot-v1-16b-a3b", data=2, model=2)
    moe = specs["layers"]["moe"]
    assert moe["w_gate"] == P(None, "model", "data", None)  # (L, E, D, F)
    assert moe["w_down"] == P(None, "model", None, "data")  # (L, E, F, D)
    assert moe["router"] == P(None, None, None)


def test_indivisible_dims_left_unsharded():
    # model axis of 512 cannot shard small smoke dims -> replicated, no error
    specs, _ = _specs_for("qwen2-1.5b", data=7, model=512)
    assert specs["layers"]["attn"]["wq"] == P(None, None, None)


def test_batch_spec_divisibility():
    assert batch_spec(256, FakeMesh(pod=2, data=16, model=16)) == ("pod", "data")
    assert batch_spec(8, FakeMesh(pod=2, data=16, model=16)) == ("pod",)
    assert batch_spec(1, FakeMesh(pod=2, data=16, model=16)) == ()
    assert batch_spec(32, FakeMesh(data=16, model=16)) == ("data",)


# ----------------------- multi-device parity (subprocess) -------------------


@pytest.mark.slow
def test_sharded_loss_matches_local():
    """Same params + batch: loss under a 2x2 mesh (FSDP+TP, MoE EP via
    shard_map) must match the single-device value."""
    run_child(
        """
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models.api import get_model
from repro.models.layers import Dist, LOCAL
from repro.sharding.specs import ShardingRules, build_param_specs, named_shardings

for arch in ("qwen2-1.5b", "moonshot-v1-16b-a3b"):
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    local_loss, _ = jax.jit(lambda p, t: model.loss_fn(p, {"tokens": t}, cfg, LOCAL))(params, tokens)

    mesh = make_mesh((2, 2), ("data", "model"))
    dist = Dist(mesh=mesh, data_axes=("data",))
    specs = build_param_specs(params, ShardingRules(mesh))
    sh = named_shardings(specs, mesh)
    params_s = jax.device_put(params, sh)
    tok_s = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
    with mesh:
        loss_s, _ = jax.jit(lambda p, t: model.loss_fn(p, {"tokens": t}, cfg, dist))(params_s, tok_s)
    d = abs(float(local_loss) - float(loss_s))
    print(arch, float(local_loss), float(loss_s), d)
    assert d < 5e-2, (arch, float(local_loss), float(loss_s))
print("OK")
""",
        devices=4,
    )


@pytest.mark.slow
def test_elastic_checkpoint_restore_across_meshes():
    """Save on a (2,2) mesh, restore onto (4,1) — elastic resume."""
    run_child(
        """
import tempfile, jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_smoke_config
from repro.models.api import get_model
from repro.sharding.specs import ShardingRules, build_param_specs, named_shardings
from repro.train.checkpoint import save_checkpoint, restore_checkpoint

cfg = get_smoke_config("qwen2-1.5b")
model = get_model(cfg)
params = model.init_params(jax.random.PRNGKey(0))

mesh1 = make_mesh((2, 2), ("data", "model"))
sh1 = named_shardings(build_param_specs(params, ShardingRules(mesh1)), mesh1)
p1 = jax.device_put(params, sh1)
with tempfile.TemporaryDirectory() as d:
    save_checkpoint(d, 1, p1)
    mesh2 = make_mesh((4, 1), ("data", "model"))
    sh2 = named_shardings(build_param_specs(params, ShardingRules(mesh2)), mesh2)
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    p2, _ = restore_checkpoint(d, 1, like, shardings=sh2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("OK")
""",
        devices=4,
    )


@pytest.mark.slow
def test_compressed_psum_matches_plain():
    run_child(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.train.compression import compressed_psum

mesh = make_mesh((4,), ("pod",))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 256))

def f(xs):
    s, res = compressed_psum(xs, "pod")
    return s, res

with mesh:
    out, res = jax.jit(shard_map(f, mesh=mesh, in_specs=P("pod", None),
                                 out_specs=(P("pod", None), P("pod", None))))(x)
want = jnp.sum(x, axis=0)
got = out[0]
rel = float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-9))
print("rel err", rel)
assert rel < 0.05  # int8 payload: ~1% quantization error
print("OK")
""",
        devices=4,
    )
