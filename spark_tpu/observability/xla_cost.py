"""XLA cost/HBM accounting off the AOT compilation API.

`jax.jit(f).lower(args).compile()` yields a Compiled whose
`cost_analysis()` (flops, bytes accessed) and `memory_analysis()`
(argument/output/temp/generated-code sizes) expose what XLA actually
allocated — the measured side of the HBM story the OOM degradation
ladder (execution/failures.py) reacts to. `peak_hbm_bytes` is the
derived per-stage demand: arguments + outputs + temps + aliases.

Everything here is best-effort: a backend that cannot answer (some
cost analyses are unimplemented per-platform) degrades to an `error`
field, never an exception — observability must not fail a query.

Capture COSTS A SECOND COMPILE of the stage (the jit call path and the
AOT path do not share an executable in-process), so the executor gates
it on `spark_tpu.sql.observability.xlaCost` and memoizes per stage key.
"""

from __future__ import annotations

from typing import Dict, Optional

#: cost_analysis keys -> event field names
_COST_FIELDS = {"flops": "flops",
                "transcendentals": "transcendentals",
                "bytes accessed": "bytes_accessed"}

_MEM_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
               "temp_size_in_bytes", "alias_size_in_bytes",
               "generated_code_size_in_bytes")


def analyze_compiled(compiled) -> Dict:
    """Flatten a Compiled's cost + memory analysis into event fields."""
    out: Dict = {}
    try:
        cost = compiled.cost_analysis()
        if cost:
            for key, name in _COST_FIELDS.items():
                if key in cost:
                    out[name] = int(cost[key])
    except Exception as e:  # noqa: BLE001 — per-platform unimplemented
        out["cost_error"] = f"{type(e).__name__}: {e}"[:160]
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            peak = 0
            for f in _MEM_FIELDS:
                v = getattr(mem, f, None)
                if v is None:
                    continue
                out[f.replace("_size_in_bytes", "_bytes")] = int(v)
                if f != "generated_code_size_in_bytes":
                    peak += int(v)
            out["peak_hbm_bytes"] = peak
    except Exception as e:  # noqa: BLE001
        out["memory_error"] = f"{type(e).__name__}: {e}"[:160]
    return out


def analyze_jit(fn, args) -> Dict:
    """Lower + compile a jitted callable for analysis only. The caller
    is responsible for fault-injection suppression (lowering re-traces
    the stage, which would double-fire trace-time chaos sites)."""
    try:
        compiled = fn.lower(*args).compile()
    except Exception as e:  # noqa: BLE001
        return {"error": f"{type(e).__name__}: {e}"[:200]}
    return analyze_compiled(compiled)


def device_hbm_capacity() -> Optional[int]:
    """Per-device memory capacity in bytes (None when the backend does
    not report it — CPU usually does not)."""
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001
        return None
    if not stats:
        return None
    for key in ("bytes_limit", "bytes_reservable_limit"):
        if key in stats:
            return int(stats[key])
    return None
