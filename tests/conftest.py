"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real single CPU device; only launch/dryrun.py forces 512."""
import importlib.util
import sys
from pathlib import Path

import pytest


@pytest.fixture(scope="session")
def small_ds():
    from repro.data.vectors import make_dataset
    return make_dataset("clustered", n=6000, d=32, n_queries=100,
                        k_gt=50, seed=0)


@pytest.fixture(scope="session")
def uniform_ds():
    from repro.data.vectors import make_dataset
    return make_dataset("uniform", n=4000, d=24, n_queries=50,
                        k_gt=20, seed=1)


@pytest.fixture(scope="session")
def built_pag(small_ds):
    from repro.core.pag import build_pag
    return build_pag(small_ds.base, p=0.2, k=8, lam=3.0, redundancy=4,
                     seed=0)


@pytest.fixture(scope="session")
def pag_store(built_pag, small_ds):
    from repro.core.search import write_partitions
    from repro.storage.simulator import ObjectStore, StorageConfig
    store = ObjectStore(StorageConfig.preset("mem"))
    write_partitions(built_pag, small_ds.base, store, n_shards=4)
    return store


@pytest.fixture(scope="module")
def smoke():
    """The repo-root ``chip_smoke.py`` script, imported as a module."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # its dataclasses resolve the module
    spec.loader.exec_module(mod)
    return mod
