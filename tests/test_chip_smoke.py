"""chip_smoke.py's phases on the CPU at a tiny size, kernels in interpret
mode: the build -> serve -> parity path the chip run takes, and the
refusal to report a result without a TPU."""
import json

import numpy as np
import pytest

from repro.data.vectors import recall_at_k


@pytest.fixture(scope="module")
def deployment(smoke):
    return smoke.build(n=2000, seed=0, n_queries=128)


@pytest.mark.parametrize("plane", ["none", "pq"])
def test_serve_phase_recall_parity_and_no_recompile(smoke, deployment,
                                                    plane):
    res = smoke.serve(deployment, plane)
    assert res.ids.shape == (128, smoke.K)
    assert len(res.batch_wall_s) == 128 // smoke.BATCH
    assert res.compiles == 0          # the warm-up compiled every shape
    assert recall_at_k(res.ids, deployment.ds.gt_ids, smoke.K) \
        >= smoke.RECALL_FLOOR
    kinds = {"none": {"l2"}, "pq": {"l2", "pq"}}[plane]
    assert set(res.launches) == kinds
    parity = smoke.check_parity(res.launches)
    for name, counts in parity.items():
        rows = res.launches["l2" if name == "l2_topk_masked" else "pq"][0]
        assert counts["rows_exact"] + counts["rows_tied"] == rows.shape[0]


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main(["--n", "2000"]) != 0
    out = capsys.readouterr().out
    assert not any(json.loads(line).get("ok")
                   for line in out.splitlines() if line.startswith("{"))


def test_same_topk_accepts_boundary_ties_only(smoke):
    d = np.array([[1.0, 2.0, 3.0]], np.float32)
    smoke._same_topk(d, np.array([[4, 5, 6]]), d, np.array([[4, 5, 7]]))
    with pytest.raises(AssertionError):  # differs before the k-th entry
        smoke._same_topk(d, np.array([[4, 5, 6]]), d,
                         np.array([[4, 8, 6]]))
