"""`BENCHMARK.json` resolves to files: every cell loads, every metric a
cell reports has its reader, and a mix the loop does not generate is
refused by name."""

import pytest

from benchmark.harness import spec
from benchmark.tests import rehearsal


@pytest.mark.parametrize("workload", rehearsal.cells())
def test_a_cell_resolves_to_its_files(workload):
    cell = spec.load_cell(workload)
    assert cell.queries and cell.end_to_end and cell.per_layer
    for kind, metrics in (("end_to_end", cell.end_to_end),
                          ("layer_metrics", cell.per_layer)):
        for m in metrics:
            assert callable(spec.module(kind, m["name"]).read), m["name"]
    for q in cell.queries:
        assert spec.module("reference", q["reference"]).CONTROLS


def test_a_mix_the_loop_does_not_generate_is_refused(monkeypatch):
    read = spec._json

    def four_clients(path):
        out = read(path)
        if "/traffic/" in path:
            out["clients"] = 4
        return out

    monkeypatch.setattr(spec, "_json", four_clients)
    with pytest.raises(SystemExit, match="4 clients"):
        spec.load_cell(rehearsal.cells()[0])
