"""Parquet string columns read dictionary-typed: `_dictionary_columns`
chooses them from the footers, `DictUnifier` unifies dictionaries and
never rows, pushed filters see the same rows as on a plain read."""

import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.dataset as pa_dataset
import pyarrow.parquet as pq
import pytest

from spark_tpu import functions as F
from spark_tpu.functions import col, lit
from spark_tpu.io.sources import (ChunkIterator, ParquetSource,
                                  _dictionary_columns, expr_to_arrow,
                                  maybe_prefetch)

CHUNK_KEY = "spark_tpu.sql.execution.streamingChunkRows"
CACHE_KEY = "spark_tpu.sql.io.deviceCacheBytes"
PREFETCH_KEY = "spark_tpu.sql.ingest.prefetch"

ROW_GROUP = 3000   # a dictionary page under a byte a row needs rows
CHUNK = 4096       # chunks that do not fall on row-group bounds


def _groups_table(groups):
    """One table of the row groups' strings, with the row group's
    ordinal `g` and a row number `v` beside them."""
    s = [x for g in groups for x in g]
    return pa.table({
        "s": pa.array(s, pa.string()),
        "g": pa.array(np.repeat(np.arange(len(groups)),
                                [len(g) for g in groups]), pa.int64()),
        "v": pa.array(np.arange(len(s)), pa.int64())})


def _cycle(values, n=ROW_GROUP):
    return [values[i % len(values)] for i in range(n)]


def _unique(tag, n):
    # 24 characters each: 60,000 of them pass the writer's 1 MiB
    return [f"{tag}-{i:019d}" for i in range(n)]


#: name -> (row groups of the string column, writer options, the
#: pushed filter if any, whether `s` is to be read dictionary-typed)
CASES = {
    "orders_differ": (
        [_cycle(["A", "N", "R"]), _cycle(["R", "A", "N"]),
         _cycle(["N", "R", "A"]), _cycle(["A", "N", "R"])],
        {}, None, True),
    "late_value": (
        [_cycle(["A", "N"]), _cycle(["N", "A"]), _cycle(["A", "N"]),
         _cycle(["N", "A", "late"])],
        {}, None, True),
    "nulls": (
        [_cycle(["A", None, "N"]), [None] * ROW_GROUP,
         _cycle([None, "R", "A"]), _cycle(["N", "R", None, None])],
        {}, None, True),
    "empty_row_group_after_filter": (
        [_cycle(["A", "N"]), _cycle(["only-in-the-filtered"]),
         _cycle(["R", "A"]), _cycle(["N", "R"])],
        {}, col("g") != lit(1), True),
    "filtered_value_gets_no_code": (
        [_cycle(["A", "N", "R"]), _cycle(["R", "X", "A"])],
        {}, col("s") != lit("X"), True),
    "written_without_dictionary": (
        [_cycle(["A", "N", "R"]), _cycle(["R", "A", "N"])],
        {"use_dictionary": False}, None, False),
    "writer_fell_back_to_plain": (
        [_unique("a", 60000), _unique("b", 60000)],
        {"row_group_size": 60000}, None, False),
    "mostly_distinct": (
        [_unique("a", ROW_GROUP), _unique("b", ROW_GROUP)],
        {}, None, False),
}


@pytest.fixture(scope="module")
def datasets(tmp_path_factory):
    root = tmp_path_factory.mktemp("dict_scan")
    out = {}
    for name, (groups, options, _flt, _typed) in CASES.items():
        path = str(root / name)
        os.makedirs(path)
        options = dict({"row_group_size": ROW_GROUP}, **options)
        pq.write_table(_groups_table(groups),
                       os.path.join(path, "t.parquet"), **options)
        out[name] = path
    return out


def _plain(path, flt):
    """The plain read, by pyarrow alone: the dataset and the filter
    as pyarrow takes it."""
    plain = pa_dataset.dataset(path, format="parquet")
    return plain, (None if flt is None
                   else expr_to_arrow(flt, plain.schema))


def _stream(chunks):
    """Every chunk's codes and validity as they were handed out, and
    the dictionary after each chunk."""
    handed, grown = [], []
    for b in chunks:
        n = int(b.num_rows())
        c = b.columns["s"]
        codes = np.asarray(c.data)[:n]
        valid = np.ones(n, bool) if c.validity is None \
            else np.asarray(c.validity)[:n]
        handed.append((codes, valid))
        grown.append(chunks.dictionaries["s"].to_pylist())
    return handed, grown


def _decode(handed, dictionary):
    out = []
    for codes, valid in handed:
        out += [dictionary[c] if ok else None
                for c, ok in zip(codes.tolist(), valid.tolist())]
    return out


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch_on", "prefetch_off"])
@pytest.mark.parametrize("case", list(CASES))
def test_streamed_chunks_decode_to_the_same_strings(session, datasets,
                                                    case, prefetch):
    _groups, _options, flt, typed = CASES[case]
    path = datasets[case]
    src = ParquetSource(path, case)
    assert pa.types.is_dictionary(
        src._dataset.schema.field("s").type) == typed
    assert src.file_schema.field("s").type == pa.string()
    assert str(src.schema().field("s").dtype) == "string"
    pushed = [] if flt is None else [flt]
    assert all(src.can_push(f) for f in pushed)
    plain, ae = _plain(path, flt)
    want = plain.to_table(filter=ae)
    expected = want.column("s").to_pylist()

    session.conf.set(PREFETCH_KEY, prefetch)
    chunks = maybe_prefetch(src.load_chunks(None, pushed, CHUNK),
                            session.conf)
    handed, grown = _stream(chunks)
    assert len(handed) == -(-len(expected) // CHUNK)
    # codes handed out earlier decode through the last dictionary
    assert _decode(handed, grown[-1]) == expected
    # the dictionary only ever grows, and holds what rows carried
    for before, after in zip(grown, grown[1:]):
        assert after[:len(before)] == before
    assert sorted(grown[-1]) == sorted(
        {x for x in expected if x is not None})
    assert len(set(grown[-1])) == len(grown[-1])

    # the parent's path: the plain read, every row hashed
    hashed = ChunkIterator(
        plain.scanner(filter=ae, batch_size=CHUNK).to_batches(), CHUNK)
    handed_p, grown_p = _stream(hashed)
    assert _decode(handed_p, grown_p[-1]) == expected

    # the resident load
    whole = src.load(None, pushed).to_arrow()
    assert whole.column("s").to_pylist() == expected
    assert whole.column("v").to_pylist() == want.column("v").to_pylist()


@pytest.mark.parametrize("scan", ["prefetch_on", "prefetch_off",
                                  "resident"])
@pytest.mark.parametrize("case", [
    c for c in CASES  # a key of distinct values has no direct domain
    if c not in ("writer_fell_back_to_plain", "mostly_distinct")])
def test_group_by_string_key(session, datasets, case, scan):
    """The direct aggregate over each dataset, streamed in chunks and
    loaded whole: the groups and their counts are pandas' over the
    plain read."""
    _groups, _options, flt, _typed = CASES[case]
    name = f"dict_scan_{case}"
    session.register_table(name, ParquetSource(datasets[case], name))
    if scan == "resident":
        session.conf.set(CHUNK_KEY, 1 << 20)
    else:
        session.conf.set(CHUNK_KEY, CHUNK)
        session.conf.set(CACHE_KEY, 0)
        session.conf.set(PREFETCH_KEY, scan == "prefetch_on")
    df = session.table(name)
    if flt is not None:
        df = df.filter(flt)
    qe = df.group_by(col("s")).agg(
        F.count(col("v")).alias("n"), F.sum(col("v")).alias("t"))._qe()
    out = qe.collect().to_pandas()
    assert any(s.name == "streaming" for s in qe.spans.spans) \
        == (scan != "resident")
    plain, ae = _plain(datasets[case], flt)
    want = plain.to_table(filter=ae).to_pandas().groupby(
        "s", dropna=False).agg(n=("v", "count"), t=("v", "sum"))
    got = out.set_index("s")
    assert sorted(map(str, got.index)) == sorted(map(str, want.index))
    for key, row in want.iterrows():
        mine = got[got.index.isna()] if pd.isna(key) else got.loc[[key]]
        assert (int(mine["n"].iloc[0]), int(mine["t"].iloc[0])) == (
            int(row["n"]), int(row["t"])), key


def test_dictionary_columns_go_by_the_footers(datasets, tmp_path):
    """Which columns: the footers' dictionary pages decide, and a
    table too small for its dictionary to pay is read plain."""
    def chosen(path):
        return _dictionary_columns(
            pa_dataset.dataset(path, format="parquet"))
    assert chosen(datasets["orders_differ"]) == ["s"]
    assert chosen(datasets["nulls"]) == ["s"]
    assert chosen(datasets["written_without_dictionary"]) == []
    assert chosen(datasets["writer_fell_back_to_plain"]) == []
    assert chosen(datasets["mostly_distinct"]) == []
    # one row group of many that fell back takes the column out
    mixed = tmp_path / "mixed"
    os.makedirs(mixed)
    pq.write_table(_groups_table([_cycle(["A", "N"])]),
                   str(mixed / "a.parquet"))
    pq.write_table(_groups_table([_unique("z", 60000)]),
                   str(mixed / "b.parquet"))
    assert chosen(str(mixed)) == []
    tiny = tmp_path / "tiny.parquet"
    pq.write_table(pa.table({"s": ["A", "N", "R"], "v": [1, 2, 3]}),
                   str(tiny))
    assert chosen(str(tiny)) == []
    assert ParquetSource(str(tiny)).load(None, []).to_arrow() \
        .column("s").to_pylist() == ["A", "N", "R"]


# -- pushdown over a dictionary-typed column --------------------------------

S = col("s")
PUSHED = {
    "eq": S == lit("N"),
    "ne": S != lit("N"),
    "lt": S < lit("N"),
    "le": S <= lit("N"),
    "gt": S > lit("N"),
    "ge": S >= lit("N"),
    "in": S.isin("A", "late", "absent"),
    "is_null": S.is_null(),
    "not_null": S.is_not_null(),
    "not_eq": ~(S == lit("A")),
    "not_in": ~S.isin("A", "N"),
    "and": (S >= lit("N")) & (col("v") < lit(7000)),
    "or": (S == lit("R")) | (col("g") == lit(0)),
    "and_or_not": ((S == lit("A")) | S.is_null()) & ~(col("g") == lit(2)),
    "literal_left": lit("N") == S,
    "absent_value": S == lit("absent"),
}


@pytest.fixture(scope="module")
def pushdown_table(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("dict_pushdown"))
    groups = [_cycle(["A", "N", None]), _cycle(["R", "N", "A"]),
              _cycle(["N", "R"]), _cycle(["A", None, "late", "R"])]
    pq.write_table(_groups_table(groups), os.path.join(path, "t.parquet"),
                   row_group_size=ROW_GROUP)
    return path


@pytest.mark.parametrize("op", list(PUSHED))
def test_pushdown_on_dictionary_column(pushdown_table, op):
    """Every operator `expr_to_arrow` emits, on a string column read
    dictionary-typed, keeps the rows it keeps on the plain read:
    through `load` and through `load_chunks`."""
    e = PUSHED[op]
    src = ParquetSource(pushdown_table, "t")
    assert pa.types.is_dictionary(src._dataset.schema.field("s").type)
    assert src.can_push(e)
    plain, ae = _plain(pushdown_table, e)
    want = plain.to_table(filter=ae)
    assert 0 < want.num_rows < plain.count_rows() or op == "absent_value"
    rows = list(zip(want.column("v").to_pylist(),
                    want.column("s").to_pylist()))

    whole = src.load(None, [e]).to_arrow()
    assert list(zip(whole.column("v").to_pylist(),
                    whole.column("s").to_pylist())) == rows

    streamed = []
    for b in src.load_chunks(None, [e], CHUNK):
        t = b.to_arrow()
        streamed += list(zip(t.column("v").to_pylist(),
                             t.column("s").to_pylist()))
    assert streamed == rows
    assert pc.sum(want.column("v")).as_py() == (
        sum(v for v, _ in streamed) if streamed else None)


def test_columns_side_by_side_give_the_serial_result(monkeypatch):
    """A chunk large enough has its columns unified and filled on
    threads of their own for the call: the same buffers as one after
    the other, the same dictionaries, and no thread left."""
    import threading
    from spark_tpu.io import sources
    n = sources._SIDE_BY_SIDE_ROWS + 5
    rng = np.random.default_rng(3)
    half = n // 2
    typed = pa.chunked_array([
        pa.DictionaryArray.from_arrays(
            pa.array(rng.integers(0, 3, half).astype(np.int32)),
            pa.array(["A", "N", "R"])),
        pa.DictionaryArray.from_arrays(
            pa.array(rng.integers(0, 3, n - half).astype(np.int32)),
            pa.array(["R", "late", "A"]))])
    table = pa.table({
        "typed": typed,
        "plain": pa.array(rng.choice(["x", "y"], n)),
        "v": pa.chunked_array([pa.array(np.arange(half)),
                               pa.array(np.arange(half, n))]),
        "w": pa.array(rng.random(n))})

    def one_chunk():
        seen = set()
        fill_column = ChunkIterator._fill_column

        def spy(self, *args):
            seen.add(threading.current_thread().name.rsplit("_", 1)[0])
            return fill_column(self, *args)

        monkeypatch.setattr(ChunkIterator, "_fill_column", spy)
        it = ChunkIterator(iter(table.to_batches()), n)
        (batch,) = list(it)
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("spark-tpu-ingest-column")]
        return batch, it.dictionaries, seen

    side, side_dicts, threads = one_chunk()
    assert threads == {"spark-tpu-ingest-column"}
    monkeypatch.setattr(sources, "_SIDE_BY_SIDE_ROWS", 1 << 62)
    serial, serial_dicts, threads = one_chunk()
    assert threads == {threading.current_thread().name.rsplit("_", 1)[0]}
    for name in table.column_names:
        assert np.array_equal(np.asarray(side.columns[name].data),
                              np.asarray(serial.columns[name].data)), name
        assert side.columns[name].validity is None
    assert side_dicts["typed"].to_pylist() == ["A", "N", "R", "late"]
    assert {k: v.to_pylist() for k, v in side_dicts.items()} \
        == {k: v.to_pylist() for k, v in serial_dicts.items()}
    out = side.to_arrow()
    assert out.column("typed").to_pylist() == typed.to_pylist()
    assert out.column("plain").to_pylist() == \
        table.column("plain").to_pylist()
    assert out.column("v").to_pylist() == list(range(n))
