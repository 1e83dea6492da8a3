"""``chip_smoke.py``: its refusal to run off the chip, and every one-chip
phase at a tiny size on the CPU backend (the same checks against pandas
that guard the real-size run on a TPU)."""

import importlib.util
import os

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_backend_that_is_not_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert "platform 'cpu'" in str(e.value.code)
    assert capsys.readouterr().out == ""      # no result line


def test_one_chip_phases_at_small_size(smoke, tmp_path):
    data_dir = str(tmp_path / "data")
    smoke.run_one_chip(jax.devices()[:1], 2048, 0, data_dir)
    assert not os.path.exists(data_dir)       # the Parquet data is removed


def test_check_rejects_a_wrong_answer(smoke):
    import numpy as np
    left = smoke.make_table_data(512, seed=3, exact_values=True)
    ref = smoke.filter_reference(left)
    got = {"k": ref.index.to_numpy(),
           "v0_sum": ref["v0_sum"].to_numpy(np.float32),
           "v0_max": ref["v0_max"].to_numpy(np.float32)}
    smoke.check(got, ref, "exact")
    got["v0_sum"] = got["v0_sum"].copy()
    got["v0_sum"][-1] += 1.0
    with pytest.raises(AssertionError, match="v0_sum"):
        smoke.check(got, ref, "off by one")
