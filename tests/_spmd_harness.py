"""Subprocess harness for tests/test_spmd.py (8 host devices)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import get_config, reduced_config
from repro.core.policy import QuantConfig
from repro.data.synthetic import DataConfig, sample_batch
from repro.dist import sharding as shard
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.optim.adamw import AdamWConfig
from repro.optim.grad_compress import compressed_psum
from repro.train.state import TrainConfig, init_state
from repro.train.train_step import make_train_step


def run_training(mesh, cfg, qcfg, tcfg, key, dcfg, n_steps=8):
    state = init_state(key, cfg, qcfg, tcfg)
    if mesh is not None:
        constrain, logits_constrain = shard.make_constrains(mesh)
        specs = shard.state_pspecs(state, mesh, qcfg)
        state_sh = shard.named_tree(specs, mesh)
        state = jax.device_put(state, state_sh)
        step = jax.jit(make_train_step(cfg, qcfg, tcfg, constrain=constrain,
                                       logits_constrain=logits_constrain),
                       in_shardings=(state_sh, None),
                       out_shardings=(state_sh, None))
    else:
        step = jax.jit(make_train_step(cfg, qcfg, tcfg))
    losses = []
    for i in range(n_steps):
        batch = sample_batch(cfg, dcfg, i, 8, 16)
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return losses, state


def main():
    assert len(jax.devices()) == 8
    mesh = make_host_mesh(model=4)
    cfg = reduced_config(get_config("granite-8b")).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=96)
    qcfg = QuantConfig(w_bits=4, a_bits=4, mode="mdq")
    tcfg = TrainConfig(total_steps=20, warmup_steps=2,
                       adamw=AdamWConfig(lr_peak=3e-3))
    dcfg = DataConfig(p_noise=0.05)
    key = jax.random.PRNGKey(0)

    losses, state = run_training(mesh, cfg, qcfg, tcfg, key, dcfg)
    losses_1dev, _ = run_training(None, cfg, qcfg, tcfg, key, dcfg)

    # compressed psum vs exact psum over the data axis
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64))
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    exact = jnp.mean(x.reshape(2, 1, 64), axis=0)

    def comp(v):
        return compressed_psum(v, "data")

    got = jax.shard_map(comp, mesh=mesh, in_specs=P("data", None),
                    out_specs=P(None, None))(xs)
    rel = float(jnp.linalg.norm(got[0] - exact[0]) / jnp.linalg.norm(exact[0]))

    # sharded decode with sequence-sharded cache
    params = state["params"]
    cache = M.init_cache(cfg, qcfg, 8, 16)
    cache = jax.device_put(cache,
                           shard.named_tree(shard.cache_pspecs(cache, mesh), mesh))
    db = {"tokens": jnp.ones((8, 1), jnp.int32),
          "pos": jnp.zeros((8,), jnp.int32)}
    dec = jax.jit(lambda p, c, b: M.decode_step(p, c, b, cfg, qcfg))
    lg, cache = dec(params, cache, db)

    print(json.dumps({
        "n_devices": len(jax.devices()),
        "losses": losses,
        "losses_1dev": losses_1dev,
        "finite": bool(np.isfinite(losses).all()),
        "psum_rel_err": rel,
        "decode_finite": bool(jnp.all(jnp.isfinite(lg))),
    }))


if __name__ == "__main__":
    main()
