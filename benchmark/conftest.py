"""Before JAX starts its backend: the CPU as eight virtual devices, as
`tests/conftest.py` gives tier-1, so that a cell with `chips` > 1
rehearses its mesh on the CPU (`benchmark/tests/rehearsal.py` run as a
script wants the flag in the environment). A flag the caller set
stands."""

import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
