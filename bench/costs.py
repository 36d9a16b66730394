"""Operations and bytes each scan kernel needs for one launch shape, and
the least time the chip could take for them.

The counts are of the work the algorithm needs at the launch shape,
whatever implements it: every pooled candidate read once, its distance
computed once, the query side and the top-k written once. Work a kernel
adds on top (widening codes, repeated selection passes) is not counted,
so it shows as a lower share of the roofline.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def l2_topk_masked(q: int, c: int, d: int, k: int, pool_itemsize: int = 4,
                   query_itemsize: int = 4) -> Tuple[int, int]:
    """(flops, bytes) of ``l2_topk_masked`` at pools [q, c, d]:
    a subtract, a multiply and an add per pooled coordinate; the pools
    at ``pool_itemsize`` bytes an element (4 for float32, 1 for uint8),
    their int32 ids and the queries at ``query_itemsize`` read, (d2, id)
    pairs written."""
    flops = 3 * q * c * d
    nbytes = pool_itemsize * q * c * d + 4 * q * c + query_itemsize * q * d \
        + 8 * q * k
    return flops, nbytes


def pq_adc_masked(q: int, c: int, m: int, k: int, code_itemsize: int = 1,
                  lut_itemsize: int = 4) -> Tuple[int, int]:
    """(flops, bytes) of ``pq_adc_masked`` at codes [q, c, m]:
    one add per looked-up entry; the codes at ``code_itemsize`` bytes
    (1 for uint8), their int32 ids and the per-query [m, 256] tables at
    ``lut_itemsize`` read, (d2, id) pairs written."""
    flops = q * c * m
    nbytes = code_itemsize * q * c * m + 4 * q * c \
        + lut_itemsize * q * m * 256 + 8 * q * k
    return flops, nbytes


KERNELS = {"l2_topk_masked": l2_topk_masked, "pq_adc_masked": pq_adc_masked}


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; a device that is not in
    ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def least_time(flops: int, nbytes: int, peak: Dict[str, float]
               ) -> Tuple[float, str]:
    """(seconds, bound): the larger of flops over peak FLOP/s and bytes
    over peak bytes/s, and which of the two it is."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
