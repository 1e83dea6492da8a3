"""4-device parity of descending sorts and ``head`` with pandas: mixed
``ascending`` flags over keys with nulls and duplicates, and the first
``n`` rows for ``n`` below, within and beyond rank 0's rows.  Asserts
internally and ends with an OK line."""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402

import repro.df as rdf  # noqa: E402
from repro.core import CylonEnv  # noqa: E402


def main():
    env = CylonEnv()
    assert env.parallelism == 4, env.parallelism
    rng = np.random.default_rng(11)
    n = 1200
    a = rng.integers(0, 25, n).astype(np.float64)
    a[rng.random(n) < 0.1] = np.nan                 # nulls in the lead key
    data = {"a": a, "b": rng.integers(0, 4, n).astype(np.int32),
            "i": np.arange(n, dtype=np.int32)}
    # ample capacity: every copy of a lead key lands on one rank, and the
    # nulls all on the last
    df = rdf.read_numpy(data, env=env, capacity=n)
    pdf = pd.DataFrame(data)
    for asc in ([False, True], [True, False], [False, False]):
        flags = asc + [True]
        q = df.sort_values(["a", "b", "i"], ascending=flags)
        want = pdf.sort_values(["a", "b", "i"], ascending=flags,
                               na_position="last")["i"].to_numpy()
        res = q.collect(env=env)
        counts = np.asarray(res.row_counts)
        got = res.to_numpy()["i"]
        np.testing.assert_array_equal(got, want)
        assert counts[0] > 0 and np.count_nonzero(counts) > 1, counts
        for k in (3, int(counts[0]), int(counts[0]) + 5, n // 2, 2 * n):
            head = q.head(k).collect(env=env)
            np.testing.assert_array_equal(head.to_numpy()["i"], want[:k])
    print("sort/limit parity OK")


if __name__ == "__main__":
    main()
