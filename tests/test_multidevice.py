"""Multi-device integration tests.

Each scenario runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set before jax
initializes (the unit-test process itself stays 1-device, per the
assignment).  Scripts assert internally and end with an OK line.
"""

import os
import subprocess
import sys

import pytest

SCRIPTS = os.path.join(os.path.dirname(__file__), "md_scripts")
SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


def _run(name: str, timeout: int = 900) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC)
    env.pop("XLA_FLAGS", None)  # script sets its own
    env["JAX_PLATFORMS"] = "cpu"  # 8 host devices; never the accelerator
    proc = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, name)],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"{name} failed\n--- stdout ---\n{proc.stdout[-4000:]}"
            f"\n--- stderr ---\n{proc.stderr[-4000:]}")
    assert "OK" in proc.stdout


@pytest.mark.multidevice
def test_comm_collectives():
    _run("comm_collectives.py")


@pytest.mark.multidevice
def test_dataframe_ops():
    _run("dataframe_ops.py")


@pytest.mark.multidevice
def test_shuffle_props():
    _run("shuffle_props.py")


@pytest.mark.multidevice
def test_sortfree_shuffle_parity():
    _run("sortfree_shuffle_parity.py")


@pytest.mark.multidevice
def test_planner_parity():
    _run("planner_parity.py")


@pytest.mark.multidevice
def test_out_of_core_parity():
    _run("out_of_core_parity.py")


@pytest.mark.multidevice
def test_string_key_parity():
    _run("string_key_parity.py")


@pytest.mark.multidevice
def test_df_frontend_parity():
    _run("df_frontend_parity.py")


@pytest.mark.multidevice
def test_sharded_train():
    _run("sharded_train.py", timeout=1800)


@pytest.mark.multidevice
def test_elastic_checkpoint():
    _run("elastic_checkpoint.py")


@pytest.mark.multidevice
def test_compression_train():
    _run("compression_train.py")


@pytest.mark.multidevice
def test_moe_shuffle_parity():
    _run("moe_shuffle_parity.py")


@pytest.mark.multidevice
def test_data_pipeline():
    _run("data_pipeline.py")


@pytest.mark.multidevice
def test_explain_analyze_fig9():
    _run("explain_analyze_fig9.py")


@pytest.mark.multidevice
def test_fault_chaos():
    _run("fault_chaos.py")


@pytest.mark.multidevice
def test_serving_stress():
    _run("serving_stress.py", timeout=1800)


@pytest.mark.multidevice
def test_ingest_parity():
    _run("ingest_parity.py")


@pytest.mark.multidevice
def test_skew_parity():
    _run("skew_parity.py")
