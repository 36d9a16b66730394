"""Training-loop behavior: loss decreases, microbatch-accumulation
equivalence, factored optimizer, gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.lm import DataConfig, batch_at
from repro.models import init_params
from repro.training.optimizer import OptimizerConfig, init_state
from repro.training.train_step import TrainConfig, make_train_step


def test_loss_decreases():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=5, total_steps=100)
    dcfg = DataConfig(seed=0, batch_size=8, seq_len=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = init_state(params, ocfg)
    step = jax.jit(make_train_step(cfg, ocfg, TrainConfig()))
    losses = []
    for s in range(30):
        params, opt, m = step(params, opt, batch_at(dcfg, cfg, s))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses


def test_microbatch_equivalence():
    """microbatches=2 produces (nearly) the same update as microbatches=1
    on the same global batch (grad averaging correctness)."""
    cfg = get_config("tinyllama-1.1b", reduced=True)
    ocfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    dcfg = DataConfig(seed=3, batch_size=8, seq_len=32)
    batch = batch_at(dcfg, cfg, 0)
    p0 = init_params(jax.random.PRNGKey(0), cfg)
    o0 = init_state(p0, ocfg)
    s1 = jax.jit(make_train_step(cfg, ocfg, TrainConfig(microbatches=1)))
    s2 = jax.jit(make_train_step(cfg, ocfg, TrainConfig(microbatches=2)))
    p1, _, m1 = s1(p0, o0, batch)
    p2, _, m2 = s2(p0, o0, batch)
    diffs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32))))
             for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2))]
    assert max(diffs) < 5e-2, max(diffs)  # bf16 params, tiny reorder noise


def test_factored_optimizer_trains():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=2, total_steps=50,
                           factored=True, min_dim_size_to_factor=32,
                           state_dtype="bfloat16")
    dcfg = DataConfig(seed=1, batch_size=8, seq_len=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = init_state(params, ocfg)
    # factored stats exist and are smaller than full second moment
    n_v = sum(x.size for x in jax.tree.leaves(opt["v"]))
    n_p = sum(x.size for x in jax.tree.leaves(params))
    assert n_v < n_p
    step = jax.jit(make_train_step(cfg, ocfg, TrainConfig()))
    losses = []
    for s in range(20):
        params, opt, m = step(params, opt, batch_at(dcfg, cfg, s))
        losses.append(float(m["loss"]))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0]


def test_mamba_trains_stably():
    """Regression: the SSD intra-chunk decay mask must clamp the exponent
    (masked exp(+large) made the backward inf*0=NaN at step 2)."""
    cfg = get_config("mamba2-370m", reduced=True)
    ocfg = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=30)
    dcfg = DataConfig(seed=0, batch_size=8, seq_len=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = init_state(params, ocfg)
    step = jax.jit(make_train_step(cfg, ocfg, TrainConfig()))
    for s in range(15):
        params, opt, m = step(params, opt, batch_at(dcfg, cfg, s))
        assert np.isfinite(float(m["loss"])), (s, m)
        assert np.isfinite(float(m["grad_norm"])), (s, m)


def test_compressed_psum_single_device():
    """shard_map int8 grad all-reduce on a trivial 1-device mesh equals
    identity within the quantization error bound."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_local_mesh
    from repro.training.compression import compressed_psum

    mesh = make_local_mesh()
    g = jax.random.normal(jax.random.PRNGKey(0), (64, 64))

    out = jax.shard_map(lambda x: compressed_psum(x, "data"), mesh=mesh,
                        in_specs=P(None, None), out_specs=P(None, None),
                        check_vma=False)(g)
    scale = float(jnp.max(jnp.abs(g))) / 127.0
    assert float(jnp.max(jnp.abs(out - g))) <= scale * 1.01
