"""Sharding rules: every spec'd dim divides its mesh axis group for every
FULL-SIZE arch config on the production meshes (no allocation needed —
AbstractMesh + eval_shape)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.distributed.sharding import DistConfig, param_specs
from repro.models import init_params


def _abstract_params(cfg):
    return jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.random.PRNGKey(0))


def _check(specs, params, mesh):
    flat_s = jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))
    flat_p = jax.tree_util.tree_leaves(params)
    assert len(flat_s) == len(flat_p)
    for (path, spec), leaf in zip(flat_s, flat_p):
        used = []
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            group = 1
            for a in axes:
                group *= mesh.shape[a]
                assert a not in used, f"axis reuse at {path}"
                used.append(a)
            assert leaf.shape[dim] % group == 0, \
                f"{path}: dim {dim} size {leaf.shape[dim]} % {group}"


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("multi_pod", [False, True])
def test_full_config_specs_divisible(arch, multi_pod):
    cfg = get_config(arch)  # FULL published config
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    mesh = AbstractMesh(shape, axes)
    params = _abstract_params(cfg)
    specs = param_specs(params, mesh, DistConfig())
    _check(specs, params, mesh)


def test_fsdp_over_pod_specs():
    cfg = get_config("kimi-k2-1t-a32b")
    mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    params = _abstract_params(cfg)
    specs = param_specs(params, mesh, DistConfig(fsdp_over_pod=True))
    _check(specs, params, mesh)


def test_big_weights_are_sharded():
    """No multi-GB leaf may end up fully replicated on the big archs."""
    for arch in ("internvl2-76b", "command-r-plus-104b", "kimi-k2-1t-a32b"):
        cfg = get_config(arch)
        mesh = AbstractMesh((16, 16), ("data", "model"))
        params = _abstract_params(cfg)
        specs = param_specs(params, mesh, DistConfig())
        flat_s = jax.tree_util.tree_leaves_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))
        flat_p = jax.tree_util.tree_leaves(params)
        for (path, spec), leaf in zip(flat_s, flat_p):
            nbytes = leaf.size * 2
            if nbytes > 2 * 2**30:
                assert any(e is not None for e in spec), \
                    f"{jax.tree_util.keystr(path)} ({nbytes/2**30:.1f} GiB) replicated"


def test_vocab_padding_multiple_128():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        assert cfg.vocab_padded % 128 == 0
        assert cfg.vocab_padded >= cfg.vocab_size
