"""Date columns, descending sorts and ``head`` (LIMIT) through ``repro.df``,
against pandas.

* Parquet ``date32`` round trip (nulls to NaT), the table's and the
  provenance's ``dates``, date-literal filters (``datetime.date`` and
  ``np.datetime64``) and date keys, and the type errors dates raise;
* int64 values outside the int32 range refused at ingest, by column;
* ``sort_values(ascending=...)`` with mixed flags over nulls and duplicate
  keys, on one device here and on four virtual devices in a subprocess
  (``md_scripts/sort_limit_parity.py``, which also runs ``head``);
* ``head(n)``, EXPLAIN's ``limit[n]`` / ``desc``, the ``limit`` operator
  scope, and the out-of-core executor's refusal of both;
* the Fig-9 query's optimized program, which the sort changes leave as it
  was.
"""

import datetime
import hashlib
import os
import re
import subprocess
import sys

import numpy as np
import pytest

pd = pytest.importorskip("pandas")

import repro.df as rdf  # noqa: E402
from repro.core import CylonEnv, Plan  # noqa: E402
from repro.dataframe.schema import DictTypeError  # noqa: E402
from repro.io import have_pyarrow, read_parquet  # noqa: E402

needs_pyarrow = pytest.mark.skipif(
    not have_pyarrow(), reason="pyarrow unavailable or REPRO_NO_PYARROW set")

HERE = os.path.dirname(os.path.abspath(__file__))
CUT = datetime.date(1995, 3, 15)


@pytest.fixture
def env():
    e = CylonEnv()
    rdf.set_default_env(e)
    yield e
    rdf.reset_default_env()


def _dated(rng, n=400, nulls=False):
    d = (np.datetime64("1995-01-01")
         + rng.integers(0, 150, n)).astype("datetime64[D]")
    if nulls:
        d[rng.random(n) < 0.1] = np.datetime64("NaT")
    return {"k": rng.integers(0, 30, n).astype(np.int32), "d": d,
            "v": rng.integers(0, 100, n).astype(np.float32)}


def _write_dated(tmp_path, data, files=2):
    import pyarrow as pa
    import pyarrow.parquet as pq
    n = len(data["k"])
    step = -(-n // files)
    for i in range(files):
        part = {c: v[i * step:(i + 1) * step] for c, v in data.items()}
        pq.write_table(pa.table(part), tmp_path / f"p{i}.parquet")
    return str(tmp_path / "*.parquet")


def _rows(cols):
    """Records in a canonical order, for comparing row multisets."""
    df = pd.DataFrame(cols)
    df = df[sorted(df.columns)]
    return df.sort_values(list(df.columns), kind="stable",
                          na_position="last").reset_index(drop=True)


# ---------------------------------------------------------------------- #
# dates
# ---------------------------------------------------------------------- #
@needs_pyarrow
def test_parquet_date32_round_trip(env, tmp_path):
    rng = np.random.default_rng(0)
    data = _dated(rng, nulls=True)
    glob = _write_dated(tmp_path, data)
    spill = read_parquet(glob, 1)
    assert spill.dates == ("d",) and spill.provenance.dates == ("d",)
    assert spill.schema["d"][0] == np.int32
    df = rdf.read_parquet(glob)
    assert "dates d" in df.explain()
    out = df.to_pandas()
    assert out["d"].dtype.kind == "M"
    got, want = _rows(out), _rows(pd.DataFrame(data))
    pd.testing.assert_series_equal(got["d"].astype("datetime64[ns]"),
                                   want["d"].astype("datetime64[ns]"))
    np.testing.assert_array_equal(got["k"], want["k"])
    raw = df.collect().to_numpy(decode=False, nulls="mask")
    assert raw["d"].dtype == np.int32
    _, st = df.collect(collect_stats=True, trace=True)
    (place,) = [sp for sp in st.trace.spans if sp.name.startswith("place:")]
    assert place.attrs["dates"] == "d"


@needs_pyarrow
@pytest.mark.parametrize("lit", [CUT, np.datetime64("1995-03-15")],
                         ids=["datetime.date", "np.datetime64"])
@pytest.mark.parametrize("op", ["<", ">=", "==", "!="])
def test_date_literal_filters_equal_pandas(env, tmp_path, lit, op):
    rng = np.random.default_rng(1)
    data = _dated(rng, nulls=True)
    df = rdf.read_parquet(_write_dated(tmp_path, data))
    pred = {"<": df.d < lit, ">=": df.d >= lit, "==": df.d == lit,
            "!=": df.d != lit}[op]
    got = df[pred].to_pandas()
    p = pd.DataFrame(data)
    stamp = pd.Timestamp("1995-03-15")
    keep = {"<": p.d < stamp, ">=": p.d >= stamp, "==": p.d == stamp,
            "!=": (p.d != stamp) & p.d.notna()}[op]
    pd.testing.assert_frame_equal(
        _rows(got)[["k", "v"]], _rows(p[keep])[["k", "v"]],
        check_dtype=False)
    assert "9204" in df[pred].explain()      # 1995-03-15, in days


def test_date_keys_groupby_join_and_minmax(env):
    rng = np.random.default_rng(2)
    data = _dated(rng)
    df = rdf.read_numpy(data)
    g = (df.groupby(["d"]).agg({"v": "sum"}).sort_values("d").to_pandas())
    want = pd.DataFrame(data).groupby("d", as_index=False)["v"].sum()
    np.testing.assert_array_equal(g["d"].to_numpy(),
                                  want["d"].to_numpy().astype("datetime64[D]"))
    np.testing.assert_allclose(g["v_sum"], want["v"])
    m = df.groupby("k").agg({"d": ["min", "max"]}).sort_values("k")
    m = m.to_pandas()
    assert m["d_min"].dtype.kind == "M" and m["d_max"].dtype.kind == "M"
    p = pd.DataFrame(data).groupby("k")["d"]
    np.testing.assert_array_equal(m["d_max"].to_numpy(),
                                  p.max().to_numpy().astype("datetime64[D]"))
    other = rdf.read_numpy({"d": np.unique(data["d"])[:20],
                            "w": np.arange(20, dtype=np.int32)}, name="o")
    j = df.merge(other, on="d").to_pandas()
    jw = pd.DataFrame(data).merge(
        pd.DataFrame({"d": np.unique(data["d"])[:20], "w": np.arange(20)}),
        on="d")
    assert len(j) == len(jw) and j["d"].dtype.kind == "M"


def test_dates_refuse_arithmetic_and_numbers(env):
    df = rdf.read_numpy(_dated(np.random.default_rng(3)))
    with pytest.raises(DictTypeError, match="not a date"):
        df[df.d < 9204].collect()
    with pytest.raises(DictTypeError, match="date value"):
        df.assign(e=df.d + 1).collect()
    with pytest.raises(DictTypeError, match="not a date"):
        df[df.v < CUT].collect()
    with pytest.raises(DictTypeError, match="date column"):
        df.groupby("k").agg({"d": "sum"}).collect()
    with pytest.raises(TypeError, match="time of day"):
        df[df.d < datetime.datetime(1995, 3, 15, 6)]


def test_read_numpy_dates_with_nat_round_trip(env):
    data = _dated(np.random.default_rng(12), nulls=True)
    df = rdf.read_numpy(data)
    out = df.sort_values("d").to_numpy()
    want = np.sort(data["d"])                      # NaT sorts last
    np.testing.assert_array_equal(out["d"], want)
    assert np.isnat(out["d"]).sum() == np.isnat(data["d"]).sum() > 0
    assert df[df.d.is_null()].to_pandas()["d"].isna().all()


@needs_pyarrow
def test_dates_out_of_core_equal_in_core(env, tmp_path):
    data = _dated(np.random.default_rng(13), n=600)
    df = rdf.read_parquet(_write_dated(tmp_path, data, files=3),
                          batch_rows=64)
    q = (df[df.d >= CUT].groupby("k").agg({"d": "max", "v": "sum"})
         .sort_values("k"))
    incore = q.to_numpy()
    ooc = q.collect(morsel_rows=64).to_numpy()
    assert ooc["d_max"].dtype == np.dtype("datetime64[D]")
    for c in incore:
        np.testing.assert_array_equal(ooc[c], incore[c])


def test_assigned_dates_stay_dates(env):
    data = _dated(np.random.default_rng(4))
    df = rdf.read_numpy(data)
    out = df.assign(e=df.d, c=CUT).to_pandas()
    assert out["e"].dtype.kind == "M" and out["c"].dtype.kind == "M"
    assert (out["c"] == pd.Timestamp(CUT)).all()


# ---------------------------------------------------------------------- #
# int64 at ingest
# ---------------------------------------------------------------------- #
@needs_pyarrow
@pytest.mark.parametrize("value,ok", [(2 ** 31 - 1, True), (2 ** 31, False),
                                      (-2 ** 31, True), (-2 ** 31 - 1, False)])
def test_int64_outside_int32_refused_at_ingest(tmp_path, value, ok):
    import pyarrow as pa
    import pyarrow.parquet as pq
    pq.write_table(pa.table({"o_orderkey": np.array([1, value], np.int64),
                             "v": np.ones(2, np.float32)}),
                   tmp_path / "t.parquet")
    if ok:
        spill = read_parquet(str(tmp_path / "t.parquet"), 1)
        assert spill.total_rows() == 2
    else:
        with pytest.raises(ValueError, match="'o_orderkey'.*int32 range"):
            read_parquet(str(tmp_path / "t.parquet"), 1)


# ---------------------------------------------------------------------- #
# descending sort and head
# ---------------------------------------------------------------------- #
def _sort_data(rng, n=300):
    a = rng.integers(0, 12, n).astype(np.float64)
    a[rng.random(n) < 0.15] = np.nan
    return {"a": a, "b": rng.integers(0, 3, n).astype(np.int32),
            "i": np.arange(n, dtype=np.int32)}


@pytest.mark.parametrize("asc", [[False, True], [True, False],
                                 [False, False], [True, True]])
def test_sort_values_mixed_ascending_equals_pandas(env, asc):
    data = _sort_data(np.random.default_rng(5))
    flags = asc + [True]
    got = (rdf.read_numpy(data).sort_values(["a", "b", "i"], ascending=flags)
           .to_pandas())
    want = pd.DataFrame(data).sort_values(["a", "b", "i"], ascending=flags,
                                          na_position="last")
    np.testing.assert_array_equal(got["i"], want["i"])
    np.testing.assert_array_equal(got["a"], want["a"])


def test_sort_values_single_flag_and_flag_count(env):
    data = _sort_data(np.random.default_rng(6))
    df = rdf.read_numpy(data)
    got = df.sort_values(["b", "i"], ascending=False).to_pandas()
    want = pd.DataFrame(data).sort_values(["b", "i"], ascending=False)
    np.testing.assert_array_equal(got["i"], want["i"])
    with pytest.raises(ValueError, match="flags"):
        df.sort_values(["a", "b"], ascending=[False])


def test_all_ascending_flags_are_the_plain_sort():
    plain = Plan.scan("t").sort(["a", "b"])
    flagged = Plan.scan("t").sort(["a", "b"], ascending=[True, True])
    assert plain.node.params == flagged.node.params
    desc = Plan.scan("t").sort(["a", "b"], ascending=[False, True])
    assert desc.node.params["ascending"] == (False, True)


@pytest.mark.parametrize("n", [0, 1, 7, 299, 300, 1000])
def test_head_after_sort_equals_pandas(env, n):
    data = _sort_data(np.random.default_rng(7))
    q = (rdf.read_numpy(data).sort_values(["a", "i"], ascending=[False, True])
         .head(n))
    want = (pd.DataFrame(data)
            .sort_values(["a", "i"], ascending=[False, True],
                         na_position="last").head(n))
    np.testing.assert_array_equal(q.to_pandas()["i"], want["i"])


def test_head_is_a_limit_node_in_explain_and_its_own_scope(env):
    from repro.obs.hlo import OPERATOR_SCOPES
    data = _sort_data(np.random.default_rng(8))
    q = (rdf.read_numpy(data).sort_values(["a", "b"], ascending=[False, True])
         .head(5))
    text = q.explain()
    assert "limit[5]" in text and "sort[a desc,b]" in text
    assert "range(a desc)" in text
    assert "limit" in OPERATOR_SCOPES
    _, st = q.collect(collect_stats=True, trace=True)
    assert "limit" in set(st.trace.op_scopes().values())
    with pytest.raises(ValueError, match="whole number"):
        rdf.read_numpy(data).head(-1)


def test_filter_above_head_stays_above_it(env):
    data = _sort_data(np.random.default_rng(9))
    df = rdf.read_numpy(data)
    q = df.sort_values("i", ascending=False).head(10)
    got = q[q.b == 1].to_pandas()
    top = pd.DataFrame(data).sort_values("i", ascending=False).head(10)
    np.testing.assert_array_equal(got["i"], top[top.b == 1]["i"])


@pytest.mark.parametrize("mode", ["bsp_staged", "amt"])
def test_sort_and_head_in_every_mode(env, mode):
    data = _sort_data(np.random.default_rng(10))
    q = (rdf.read_numpy(data).sort_values(["a", "i"], ascending=[False, True])
         .head(20))
    want = (pd.DataFrame(data).sort_values(["a", "i"], ascending=[False, True],
                                           na_position="last").head(20))
    np.testing.assert_array_equal(q.to_pandas(mode=mode)["i"], want["i"])


@pytest.mark.parametrize("what", ["limit", "descending"])
def test_out_of_core_refuses_limit_and_descending_sort(env, what):
    data = _sort_data(np.random.default_rng(11))
    df = rdf.read_numpy(data, spill=True, chunk_rows=64)
    q = (df.sort_values("i").head(3) if what == "limit"
         else df.sort_values("i", ascending=False))
    with pytest.raises(NotImplementedError, match=what):
        q.collect(morsel_rows=64)
    # the ascending sort alone still streams
    out = df.sort_values("i").collect(morsel_rows=64).to_numpy()
    np.testing.assert_array_equal(out["i"], np.arange(300))


def test_sort_and_head_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(HERE, os.pardir, "src"))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "md_scripts",
                                      "sort_limit_parity.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "OK" in p.stdout


# ---------------------------------------------------------------------- #
# the Fig-9 program
# ---------------------------------------------------------------------- #
#: instructions (metadata left out) of the Fig-9 query's optimized program
#: on one CPU device at 2,048 rows per table, as the ascending-only sort
#: built it: 1,996 of them and their SHA-1
FIG9_PROGRAM = (1996, "fe1b0c907e169650b4a2cc3d05c5e41613171c88")


def test_fig9_program_unchanged_by_descending_sorts():
    sys.path.insert(0, os.path.join(HERE, os.pardir))
    from chipbench.spec import load_module, resolve
    fig9 = load_module("recipes", "fig9")
    spec = resolve("fig9-1chip.incore").traffic["query"]
    import jax
    env = CylonEnv(jax.devices()[:1])
    frames = {name: rdf.read_numpy(fig9.make_table_data(2048, [5, i]),
                                   env=env, name=name)
              for i, name in enumerate(["left", "right"])}
    q = load_module("queries", "oplist").build_frame(
        frames, spec, {"in_core_rows_per_chip": 2048})
    q.collect(env=env, mode="bsp")
    (key,) = list(env._cache)
    ins = [re.sub(r", metadata=\{[^}]*\}", "", line)
           for line in env.program_text(key).splitlines()
           if re.match(r"\s*(ROOT\s+)?%|(ENTRY\s+)?%", line)]
    digest = hashlib.sha1("\n".join(ins).encode()).hexdigest()
    assert (len(ins), digest) == FIG9_PROGRAM
