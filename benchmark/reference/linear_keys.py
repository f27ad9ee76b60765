"""Plain reference for upstream's "Aggregate w keys": `range(N)`
grouped by `id & (groups - 1)`, `sum` of the key.

Closed form, as `chip_smoke.py` checks it: every key `k` occurs
`N / groups` times, so `sum(k) = k * N / groups`. numpy, int64, exact.

`precision="float32"` is the control that must fail the comparison: the
same sums accumulated row block by row block in float32, as an
accumulator of that type on the device would hold them. (float64 and
int32 hold every sum of this shape exactly, so float32 is the nearest
type below that can differ.)
"""

from __future__ import annotations

from typing import Dict

import numpy as np

KEYS = ["k"]

#: the precisions below the configuration's exact int64 sums
CONTROLS = ("float32",)


def compute(config: Dict, tables: Dict[str, str], pool,
            precision: str = "exact") -> Dict:
    n, groups = int(config["rows"]), int(config["groups"])
    if n % groups:
        raise ValueError(f"{n} rows do not divide into {groups} keys")
    k = np.arange(groups, dtype=np.int64)
    if precision == "exact":
        total = k * (n // groups)
    else:
        f = np.dtype(precision)
        acc = np.zeros(groups, dtype=f)
        row = k.astype(f)
        for _ in range(n // groups):  # one pass of the keys per step
            acc += row
        total = acc
    return {"keys": KEYS, "table": {"k": k, "sum(k)": total}}
