"""`tpch-sf10-q5-mesh4.q5`'s whole stage compiled for a described 2x2
v5e at SF10's shapes, not run: its memory analysis and its shapes. The
plan is made on the CPU's four virtual devices over the tests' SF0.01
tables with SF10's row estimates and key domains
(`tests/test_q5_mesh.py::at_sf10`), the scans' shapes are scaled 1024x
(LINEITEM's 16 Ki slots a shard become SF10's 16 Mi), and the exchange
blocks are the seeded ones or those a converged SF10 execution learns
(`--learned`). Some four minutes of a CPU host a compile; the compiled
text is left beside this file.

    python3 _archive/q5_mesh_stage.py [--learned]"""
import os
import shutil
import sys
import tempfile
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

#: the blocks a converged execution learns at SF10: bucket_capacity of
#: each exchange's fullest bucket (LINEITEM's 3.75 M rows, ORDERS' 1994
#: rows by o_custkey, CUSTOMER's, the ORDERS-CUSTOMER join's by
#: o_orderkey); NATION's two stay under the threshold
LEARNED = {"e0": 1 << 22, "e1": 1 << 18, "e4": 1 << 17, "e5": 1 << 15}


def at_sf10():
    from spark_tpu.io.sources import ParquetSource
    from tests.test_q5_mesh import KEYS, SCALED, SF10
    rows, stats = ParquetSource.estimated_rows, ParquetSource.column_stats

    def estimated_rows(self):
        n = rows(self)
        return n * SF10 if self.name in SCALED else n

    def column_stats(self):
        found = stats(self)
        if not found or self.name not in SCALED:
            return found
        out = {}
        for name, s in found.items():
            s = dict(s)
            if name.endswith(KEYS) and s.get("max") is not None:
                s["max"] = s["max"] * SF10
            out[name] = s
        return out

    ParquetSource.estimated_rows = estimated_rows
    ParquetSource.column_stats = column_stats


def main():
    jax.config.update("jax_enable_compilation_cache", False)
    from tests.test_q5 import _write
    from tests.test_q5_mesh import SEED
    learned = "--learned" in sys.argv
    at_sf10()
    data = tempfile.mkdtemp(prefix="q5_mesh_stage_",
                            dir=os.path.dirname(os.path.abspath(__file__)))
    try:
        compile_stage(_write(data, SEED), learned)
    finally:
        shutil.rmtree(data, ignore_errors=True)


def compile_stage(tables, learned):
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from spark_tpu import Conf
    from spark_tpu.execution.executor import QueryExecution
    from spark_tpu.io.sources import ParquetSource
    from spark_tpu.parallel.mesh import AXIS
    from spark_tpu.plan import physical as P
    from spark_tpu.session import SparkTpuSession
    from tests.test_q5_mesh import SCALED
    from tests.test_resident_served import _text
    session = SparkTpuSession(
        conf=Conf().set("spark_tpu.sql.mesh.size", 4)
        .set("spark_tpu.execution.meshFallback.enabled", False),
        register_active=False)
    for name, d in tables.items():
        session.register_table(name, ParquetSource(d, name))
    # the root and the arguments the executor hands its stage
    seen = {}
    analyze = QueryExecution._analyze_jaxpr_phase

    def grab(self, root, mesh, args):
        seen.update(qe=self, root=root, args=args)
        return analyze(self, root, mesh, args)

    QueryExecution._analyze_jaxpr_phase = grab
    session.sql(_text("q5"))._qe().collect()
    qe, root, (batches, _token) = seen["qe"], seen["root"], seen["args"]
    scans = []
    qe._collect_scans(root, scans)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:4]), (AXIS,))
    sharded = NamedSharding(mesh, PartitionSpec(AXIS))

    def described(batch, name):
        k = 1024 if name in SCALED else 1
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((x.shape[0] * k,) + x.shape[1:],
                                           x.dtype, sharding=sharded),
            batch)

    args = [described(b, s.source.name) for b, s in zip(batches, scans)]
    token = jax.ShapeDtypeStruct((4,), np.int32, sharding=sharded)
    for node in qe._nodes(root, P.ExchangeExec):
        node.block_cap = LEARNED.get(node.tag) if learned else None
    t0 = time.time()
    compiled = jax.jit(qe._build_stage_fn(root, mesh)).lower(
        args, token).compile()
    which = "learned" if learned else "seeded"
    print(which, "compile_s", round(time.time() - t0, 1))
    print(which, compiled.memory_analysis())
    host = session._stage_host.get(qe._stage_key(root, mesh), {})
    print(which, "exch_blocks", {k[len("exch_block_"):]: v
                                 for k, v in host.items()
                                 if k.startswith("exch_block_")})
    text = compiled.as_text()
    print(which, "arrays of 32 Mi / 16 Mi slots in the text:",
          text.count("[33554432]"), text.count("[16777216]"))
    out = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(out, f"q5_mesh_{which}.hlo.txt"), "w") as f:
        f.write(text)


if __name__ == "__main__":
    main()
