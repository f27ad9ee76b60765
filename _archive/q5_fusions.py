"""PR 41 scratch: who owns a fusion of Q5's compacted stage. In
process, the cell's data: Q5 twice (the capacities learned, then
applied), then the stage that ran, lowered again and compiled (the
persistent cache has it), and for each instruction named on the
command line the JAX operations its body came from
(`metadata={op_name=...}`), which name the engine's operator by its
`jax.named_scope` or by the jnp call. `--small` rehearses on the CPU.

python3 _archive/q3_fusions.py <seed> fusion.45 fusion.38 ..."""
import collections
import os
import re
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)
from benchmark.harness import cell as C, spec  # noqa: E402


def bodies(text):
    """computation name -> its lines; instruction name -> its line."""
    comps, lines, cur = {}, {}, None
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ENTRY )?%?([\w.-]+) \(.*\) -> .* \{$", ln)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        if cur is not None:
            cur.append(ln)
        m = re.match(r"\s*(?:ROOT )?%([\w.-]+) = ", ln)
        if m:
            lines[m.group(1)] = ln
    return comps, lines


def main():
    args = [a for a in sys.argv[1:] if a != "--small"]
    seed, wanted = int(args[0]), args[1:]
    if "--small" in sys.argv:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.path.insert(0, os.path.join(CHECKOUT, "benchmark", "tests"))
        from benchmark.tests.rehearsal import small_cell
        cell = small_cell("tpch-sf1-q5.q5")
    else:
        cell = spec.load_cell("tpch-sf1-q5.q5")
    with C.worker_pool(cell) as pool:
        data = C.submit_data(cell, pool, seed,
                             os.path.join(CHECKOUT, "benchmark", "data"))
        tables, _rows = C.finish_data(data)
    from spark_tpu import Conf
    from spark_tpu.io.sources import ParquetSource
    from spark_tpu.session import SparkTpuSession
    from spark_tpu.testing.stage_lowering import lower_stage
    session = SparkTpuSession(conf=Conf(), register_active=False)
    for name, path in tables.items():
        session.register_table(name, ParquetSource(path, name))
    sql = cell.queries[0]["text"]
    for _ in range(2):
        qe = session.sql(sql)._qe()
        qe.execute_batch()
    print("plan:", [ln.strip()[:160] for ln in
                    qe.executed_plan.tree_string().splitlines()
                    if "RuntimeFilter" in ln])
    text = lower_stage(qe).compile().as_text()
    os.makedirs(os.path.join(CHECKOUT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(CHECKOUT, "chiprun_out", "q5_stage.hlo.txt"),
              "w") as f:
        f.write(text)
    comps, lines = bodies(text)
    for name in wanted or sorted(n for n in lines if "fusion" in n)[:5]:
        line = lines.get(name)
        if line is None:
            print(name, "not in the text")
            continue
        called = re.search(r"calls=%?([\w.-]+)", line)
        body = comps.get(called.group(1), []) if called else []
        ops = collections.Counter(
            m.group(1) for ln in body + [line]
            for m in [re.search(r'op_name="([^"]+)"', ln)] if m)
        kinds = collections.Counter(
            m.group(1) for ln in body
            for m in [re.search(r"= \S+ ([\w-]+)\(", ln)] if m)
        shape = re.search(r"= (\S+) fusion", line)
        print(name, shape.group(1)[:80] if shape else "", dict(kinds))
        print("    ", re.sub(r", (metadata|backend_config)=.*", "",
                             line.strip())[:400])
        for op, n in ops.most_common(8):
            print("    ", n, op[-150:])


if __name__ == "__main__":
    main()
