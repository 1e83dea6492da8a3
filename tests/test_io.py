"""Tests for ``repro.io``: Parquet/CSV ingest into the spill format.

* multi-file Parquet with nulls in key AND value columns vs a pandas
  oracle (records identical after canonical re-ordering),
* repeat-read bit-identity + the process-level dictionary cache
  (second read: cache hit, zero recodes, identical physical layout),
* incremental dictionary growth across files (a later file introduces a
  lexicographically-earlier key -> stale chunks recoded at finalize),
* both CSV lanes (pyarrow streaming / pure-python fallback via
  ``REPRO_NO_PYARROW``) agree, including numeric int->float promotion,
* ``from_pandas`` with mixed NaN / ``None`` round-trips (regression),
* frontend ``dropna`` / ``fillna`` / ``isna`` vs pandas,
* EXPLAIN renders ``scan[parquet: N files, ~M rows]`` and EXPLAIN
  ANALYZE reports the scan ingest stage; ``ExecStats.rows_read``.

pyarrow-dependent tests skip when it is absent (satellite CI lane runs
this file with ``REPRO_NO_PYARROW=1`` to exercise the fallback paths).
"""

import os

import numpy as np
import pytest

pd = pytest.importorskip("pandas")

import repro.df as rdf  # noqa: E402
from repro.core import CylonEnv  # noqa: E402
from repro.io import (DictionaryCache, IngestInfo, have_pyarrow,  # noqa: E402
                      read_csv, read_parquet)
from repro.nulls import mask_name  # noqa: E402

needs_pyarrow = pytest.mark.skipif(
    not have_pyarrow(), reason="pyarrow unavailable or REPRO_NO_PYARROW set")


@pytest.fixture
def env():
    e = CylonEnv()
    rdf.set_default_env(e)
    yield e
    rdf.reset_default_env()


def _write_parquet(path, cols):
    import pyarrow as pa
    import pyarrow.parquet as pq
    pq.write_table(pa.table(cols), str(path))


def _pq_dataset(tmp_path, nfiles=3, rows=20):
    """nfiles Parquet files: unique ``i``, nullable string key ``k``,
    nullable float ``v``, nullable int ``n``.  Returns (paths, oracle)."""
    rng = np.random.default_rng(11)
    paths, frames = [], []
    for f in range(nfiles):
        i = np.arange(f * rows, (f + 1) * rows)
        k = [f"key{rng.integers(0, 8):02d}" if rng.random() > 0.2 else None
             for _ in range(rows)]
        v = [float(rng.integers(0, 50)) if rng.random() > 0.2 else None
             for _ in range(rows)]
        n = [int(rng.integers(0, 9)) if rng.random() > 0.2 else None
             for _ in range(rows)]
        p = tmp_path / f"part{f}.parquet"
        _write_parquet(p, {"i": i, "k": k, "v": v, "n": n})
        paths.append(str(p))
        frames.append(pd.DataFrame({"i": i, "k": k, "v": v, "n": n}))
    oracle = pd.concat(frames, ignore_index=True)
    return paths, oracle


def _by_id(cols):
    """Re-order ingested columns by the unique ``i`` id (round-robin
    chunking permutes global row order legitimately)."""
    order = np.argsort(np.asarray(cols["i"]))
    return {c: np.asarray(cols[c], dtype=object)[order] for c in cols}


def _assert_records_equal(got, want_df):
    got = _by_id(got)
    for c in want_df.columns:
        w = want_df[c].to_numpy()
        g = got[c]
        for a, b in zip(g, w):
            a_null = a is None or (isinstance(a, float) and np.isnan(a))
            b_null = b is None or (isinstance(b, float) and np.isnan(b))
            assert a_null == b_null, (c, a, b)
            if not a_null:
                assert a == b, (c, a, b)


# --------------------------------------------------------------------- #
# Parquet ingest
# --------------------------------------------------------------------- #
@needs_pyarrow
def test_read_parquet_multi_file_with_nulls(tmp_path):
    paths, oracle = _pq_dataset(tmp_path)
    spill = read_parquet(paths, parallelism=2, batch_rows=8,
                         dict_cache=DictionaryCache())
    assert spill.total_rows() == len(oracle)
    info = spill.provenance
    assert isinstance(info, IngestInfo)
    assert info.format == "parquet"
    assert len(info.files) == 3 and info.rows == len(oracle)
    assert info.bytes_read == sum(os.path.getsize(p) for p in paths)
    assert info.batches >= 3 and not info.dict_cache_hit
    assert str(info) == f"parquet: 3 files, ~{len(oracle)} rows"
    _assert_records_equal(spill.to_numpy(), oracle)
    # physical layout invariants: masks exist, null slots hold zeros
    raw = spill.to_numpy(decode=False, nulls="mask")
    for c in ("k", "v", "n"):
        m = raw[mask_name(c)]
        assert m.dtype == np.bool_ and not m.all()
        assert not np.asarray(raw[c])[~m].any(), c


@needs_pyarrow
def test_read_parquet_glob_and_columns(tmp_path):
    paths, oracle = _pq_dataset(tmp_path)
    spill = read_parquet(str(tmp_path / "*.parquet"), parallelism=2,
                         columns=["i", "v"], dict_cache=DictionaryCache())
    assert spill.provenance.files == tuple(sorted(paths))
    got = spill.to_numpy()
    assert set(got) == {"i", "v"}
    _assert_records_equal(got, oracle[["i", "v"]])


@needs_pyarrow
def test_read_parquet_missing_source(tmp_path):
    with pytest.raises(FileNotFoundError):
        read_parquet(str(tmp_path / "nope-*.parquet"), parallelism=2)


@needs_pyarrow
def test_read_parquet_empty_dataset(tmp_path):
    import pyarrow as pa
    schema = pa.schema([("i", pa.int64()), ("k", pa.string())])
    _write_parquet(tmp_path / "empty.parquet",
                   pa.table({"i": [], "k": []}, schema=schema))
    spill = read_parquet(str(tmp_path / "empty.parquet"), parallelism=2,
                         dict_cache=DictionaryCache())
    assert spill.total_rows() == 0
    assert set(spill.column_names) >= {"i", "k"}
    assert spill.dictionaries["k"] == ("",)


@needs_pyarrow
def test_repeat_read_cache_hit_and_bit_identity(tmp_path):
    paths, _ = _pq_dataset(tmp_path)
    cache = DictionaryCache()
    s1 = read_parquet(paths, parallelism=2, batch_rows=8, dict_cache=cache)
    assert cache.misses == 1 and cache.hits == 0
    s2 = read_parquet(paths, parallelism=2, batch_rows=8, dict_cache=cache)
    assert cache.hits == 1
    assert s2.provenance.dict_cache_hit
    # cached dictionaries are final from batch one -> nothing to recode
    assert s2.provenance.recodes == 0
    assert s1.dictionaries == s2.dictionaries
    a = s1.to_numpy(decode=False, nulls="mask")
    b = s2.to_numpy(decode=False, nulls="mask")
    assert set(a) == set(b)
    for c in a:
        np.testing.assert_array_equal(a[c], b[c], err_msg=c)


@needs_pyarrow
def test_cache_invalidated_by_rewrite(tmp_path):
    paths, _ = _pq_dataset(tmp_path, nfiles=1)
    cache = DictionaryCache()
    read_parquet(paths, parallelism=1, dict_cache=cache)
    # rewrite with different content: size/mtime key no longer matches
    _write_parquet(paths[0], {"i": np.arange(4), "k": ["zz", None, "a", "b"],
                              "v": [1.0, None, 3.0, 4.0],
                              "n": [1, 2, None, 4]})
    s = read_parquet(paths, parallelism=1, dict_cache=cache)
    assert not s.provenance.dict_cache_hit
    assert cache.misses == 2
    assert s.dictionaries["k"] == ("a", "b", "zz")


@needs_pyarrow
def test_incremental_dictionary_growth_recodes(tmp_path):
    # file2 introduces a lexicographically-earlier key, so every code
    # assigned while reading file1 is stale and must be remapped
    _write_parquet(tmp_path / "a.parquet", {"k": ["m", "z", None, "m"]})
    _write_parquet(tmp_path / "b.parquet", {"k": ["a", "m", "a", None]})
    spill = read_parquet([str(tmp_path / "a.parquet"),
                          str(tmp_path / "b.parquet")],
                         parallelism=2, dict_cache=DictionaryCache())
    assert spill.dictionaries["k"] == ("a", "m", "z")
    assert spill.provenance.recodes >= 1
    got = spill.to_numpy()
    vals = sorted(x for x in got["k"] if x is not None)
    assert vals == ["a", "a", "m", "m", "m", "z"]
    assert sum(x is None for x in got["k"]) == 2
    # null slots are canonical code 0 even after the remap
    raw = spill.to_numpy(decode=False, nulls="mask")
    assert not raw["k"][~raw[mask_name("k")]].any()


@needs_pyarrow
def test_all_null_string_column(tmp_path):
    import pyarrow as pa
    _write_parquet(tmp_path / "n.parquet",
                   pa.table({"i": [1, 2, 3],
                             "s": pa.array([None, None, None],
                                           type=pa.string())}))
    spill = read_parquet(str(tmp_path / "n.parquet"), parallelism=1,
                         dict_cache=DictionaryCache())
    assert spill.dictionaries["s"] == ("",)
    got = spill.to_numpy()
    assert all(x is None for x in got["s"])


# --------------------------------------------------------------------- #
# CSV ingest (both lanes)
# --------------------------------------------------------------------- #
def _write_csv_dataset(tmp_path):
    (tmp_path / "a.csv").write_text(
        "i,k,v\n0,alpha,1.5\n1,,\n2,beta,3.0\n3,alpha,\n")
    (tmp_path / "b.csv").write_text(
        "i,k,v\n4,gamma,2.5\n5,beta,\n6,,0.5\n")
    oracle = pd.DataFrame({
        "i": [0, 1, 2, 3, 4, 5, 6],
        "k": ["alpha", None, "beta", "alpha", "gamma", "beta", None],
        "v": [1.5, None, 3.0, None, 2.5, None, 0.5]})
    return [str(tmp_path / "a.csv"), str(tmp_path / "b.csv")], oracle


def test_read_csv_python_lane(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_NO_PYARROW", "1")
    assert not have_pyarrow()
    paths, oracle = _write_csv_dataset(tmp_path)
    spill = read_csv(paths, parallelism=2, batch_rows=3,
                     dict_cache=DictionaryCache())
    assert spill.provenance.format == "csv"
    _assert_records_equal(spill.to_numpy(), oracle)


@needs_pyarrow
def test_csv_lanes_agree(tmp_path, monkeypatch):
    paths, oracle = _write_csv_dataset(tmp_path)
    arrow = read_csv(paths, parallelism=2, dict_cache=DictionaryCache())
    _assert_records_equal(arrow.to_numpy(), oracle)
    monkeypatch.setenv("REPRO_NO_PYARROW", "1")
    python = read_csv(paths, parallelism=2, dict_cache=DictionaryCache())
    a, b = _by_id(arrow.to_numpy()), _by_id(python.to_numpy())
    assert set(a) == set(b)
    assert arrow.dictionaries == python.dictionaries
    for c in a:
        for x, y in zip(a[c], b[c]):
            assert (x is None) == (y is None), c
            if x is not None:
                assert x == y or (np.isnan(x) and np.isnan(y)), c


def test_csv_python_lane_numeric_promotion(tmp_path, monkeypatch):
    # first file parses x as int64, second needs float: widen at finalize
    monkeypatch.setenv("REPRO_NO_PYARROW", "1")
    (tmp_path / "a.csv").write_text("i,x\n0,1\n1,2\n")
    (tmp_path / "b.csv").write_text("i,x\n2,3.5\n3,\n")
    spill = read_csv([str(tmp_path / "a.csv"), str(tmp_path / "b.csv")],
                     parallelism=1, dict_cache=DictionaryCache())
    got = _by_id(spill.to_numpy())
    want = [1.0, 2.0, 3.5, None]
    for g, w in zip(got["x"], want):
        if w is None:
            assert np.isnan(g)
        else:
            assert g == w


def test_csv_header_mismatch(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_NO_PYARROW", "1")
    (tmp_path / "a.csv").write_text("i,x\n0,1\n")
    (tmp_path / "b.csv").write_text("i,y\n1,2\n")
    with pytest.raises(ValueError, match="header"):
        read_csv([str(tmp_path / "a.csv"), str(tmp_path / "b.csv")],
                 parallelism=1, dict_cache=DictionaryCache())


# --------------------------------------------------------------------- #
# from_pandas nulls (regression) + frontend missing-data ops
# --------------------------------------------------------------------- #
def test_from_pandas_mixed_nan_none(env):
    pdf = pd.DataFrame({
        "a": [1.0, np.nan, 3.0, np.nan],
        "s": ["x", None, "y", None],
        "b": [10, 20, 30, 40]})          # no nulls: stays int, no mask
    out = rdf.from_pandas(pdf).to_pandas()
    out = out.sort_values("b").reset_index(drop=True)
    assert list(out["b"]) == [10, 20, 30, 40]
    np.testing.assert_array_equal(out["a"], pdf["a"])   # NaN==NaN here
    # missing strings read back as pandas itself spells them (pandas 3
    # infers the ``str`` dtype, whose missing value is NaN)
    pd.testing.assert_series_equal(out["s"], pdf["s"])
    raw = rdf.from_pandas(pdf).to_numpy(nulls="mask")
    assert mask_name("a") in raw and mask_name("s") in raw
    assert mask_name("b") not in raw


def test_frontend_dropna_fillna_isna(env):
    pdf = pd.DataFrame({"k": [1, 2, 3, 4, 5],
                        "a": [1.0, np.nan, 3.0, np.nan, 5.0],
                        "b": [np.nan, 2.0, 3.0, np.nan, 5.0]})
    df = rdf.from_pandas(pdf)

    got = df.dropna().to_pandas().sort_values("k").reset_index(drop=True)
    want = pdf.dropna().reset_index(drop=True)
    assert list(got["k"]) == list(want["k"])

    got = (df.dropna(subset=["a"]).to_pandas()
           .sort_values("k").reset_index(drop=True))
    want = pdf.dropna(subset=["a"]).reset_index(drop=True)
    assert list(got["k"]) == list(want["k"])
    np.testing.assert_array_equal(got["b"], want["b"])

    got = (df.fillna(0.0, subset=["a", "b"]).to_pandas()
           .sort_values("k").reset_index(drop=True))
    want = pdf.fillna(0.0)
    np.testing.assert_array_equal(got["a"], want["a"])
    np.testing.assert_array_equal(got["b"], want["b"])

    got = (df.isna(subset=["a", "b"]).to_pandas()
           .sort_values("k").reset_index(drop=True))
    np.testing.assert_array_equal(got["a"].astype(bool), pdf["a"].isna())
    np.testing.assert_array_equal(got["b"].astype(bool), pdf["b"].isna())


def test_dropna_elided_for_non_null_columns(env):
    # no masks anywhere: the optimizer proves the is_null checks false
    df = rdf.read_numpy({"k": np.arange(8, dtype=np.int32),
                         "v": np.ones(8, np.float32)})
    text = df.dropna().explain()
    assert "null-elision: is_null(k) is always false" in text, text
    assert "null-elision: is_null(v) is always false" in text, text


# --------------------------------------------------------------------- #
# EXPLAIN / EXPLAIN ANALYZE / ExecStats surfacing
# --------------------------------------------------------------------- #
@needs_pyarrow
def test_explain_scan_source_label(env, tmp_path):
    paths, oracle = _pq_dataset(tmp_path)
    df = rdf.read_parquet(paths, dict_cache=DictionaryCache())
    text = df.dropna(subset=["k"]).explain()
    assert f"scan[parquet: 3 files, ~{len(oracle)} rows]" in text, text


@needs_pyarrow
def test_explain_analyze_scan_stage_and_stats(env, tmp_path):
    paths, oracle = _pq_dataset(tmp_path)
    df = rdf.read_parquet(paths, dict_cache=DictionaryCache())
    q = df.dropna(subset=["k"]).groupby("k").agg({"v": "sum"})
    out, stats = q.collect(collect_stats=True)
    assert stats.rows_read == len(oracle)
    assert stats.bytes_read == sum(os.path.getsize(p) for p in paths)
    assert stats.rows_dropped == 0
    text = df.dropna(subset=["k"]).groupby("k").agg(
        {"v": "sum"}).explain_analyze()
    assert "stage scan: ingested" in text, text
    assert f"{len(oracle)} rows" in text, text


# --------------------------------------------------------------------- #
# End-to-end: Parquet -> merge/groupby/sort pipeline vs pandas (1 device)
# --------------------------------------------------------------------- #
@needs_pyarrow
def test_parquet_pipeline_vs_pandas(env, tmp_path):
    paths, oracle = _pq_dataset(tmp_path, nfiles=2, rows=24)
    _write_parquet(tmp_path / "dim.parquet",
                   {"k": [f"key{i:02d}" for i in range(8)] + [None],
                    "w": [float(i) for i in range(8)] + [None]})
    facts = rdf.read_parquet(paths, dict_cache=DictionaryCache())
    dim = rdf.read_parquet(str(tmp_path / "dim.parquet"),
                           dict_cache=DictionaryCache())
    q = (facts.merge(dim, on="k", out_capacity=512)
         .groupby("k").agg({"v": ["sum", "count"], "w": "max"})
         .sort_values("k"))
    # engine semantics: null keys never match / never form a group
    pdim = pd.DataFrame({"k": [f"key{i:02d}" for i in range(8)] + [None],
                         "w": [float(i) for i in range(8)] + [None]})
    m = oracle.dropna(subset=["k"]).merge(pdim.dropna(subset=["k"]), on="k")
    want = (m.groupby("k")
            .agg(v_sum=("v", "sum"), v_count=("v", "count"),
                 w_max=("w", "max"))
            .reset_index().sort_values("k").reset_index(drop=True))
    ref = None
    for mode in ("bsp", "bsp_staged", "amt"):
        out, stats = q.collect(mode=mode, collect_stats=True)
        assert stats.rows_dropped == 0, (mode, stats)
        got = out.to_numpy()
        assert list(got["k"]) == list(want["k"]), mode
        np.testing.assert_allclose(got["v_sum"], want["v_sum"], rtol=1e-6)
        np.testing.assert_array_equal(got["v_count"],
                                      want["v_count"].to_numpy())
        np.testing.assert_array_equal(got["w_max"], want["w_max"])
        if ref is None:
            ref = got
        else:
            for c in ref:   # bit-identical across in-core modes
                np.testing.assert_array_equal(ref[c], got[c],
                                              err_msg=(mode, c))
    # out-of-core over morsels: keys/counts exact, float aggs to tolerance
    spill, stats = q.collect(morsel_rows=8, collect_stats=True)
    assert stats.rows_dropped == 0 and stats.morsels > 1, stats
    got = spill.to_numpy()
    assert list(got["k"]) == list(want["k"])
    np.testing.assert_array_equal(got["v_count"], want["v_count"].to_numpy())
    np.testing.assert_allclose(got["v_sum"], want["v_sum"], rtol=1e-5)
