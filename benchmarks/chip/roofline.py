"""The chip's peaks and the least time a decomposition's work needs.

The work is counted from the graph alone, by the reference peel, so it
reads the same whatever implementation does it:

* operations — two for every wedge visited: each wedge of the static
  graph once to count butterflies (``sum_v d_v (d_v - 1)``), and each
  wedge still present when a vertex peels, to update its neighbours'
  supports (the reference's ``wedges_peel``);
* bytes — what any implementation must move at least once: the edge
  list (two int32 endpoints an edge), and a support and a tip number
  (four bytes each) per peeled vertex.

The least time is the larger of operations over the chip's peak rate and
bytes over its memory bandwidth; ``bound`` says which.  The peak rate is
the bf16 matrix peak, the highest the chip has for floating point, so
the share can only be understated.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (have {sorted(table)})")
    return table[device_kind]


def decomposition_work(wedges_count: int, wedges_peel: int, n_u: int,
                       m: int) -> Dict[str, float]:
    return {"ops": 2.0 * (wedges_count + wedges_peel),
            "bytes": 8.0 * m + 8.0 * n_u}


def least_time(work: Dict[str, float], peak: Dict) -> Dict:
    compute = work["ops"] / float(peak["bf16_flops_per_s"])
    memory = work["bytes"] / float(peak["hbm_bytes_per_s"])
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
