"""Hardware-free coverage of every TPU autodetect tier and scheduling
helper (reference: `python/ray/tests/accelerators/test_tpu.py:14-264`).

Each detection tier is exercised by mocking its probe surface: env fakes,
/dev/accel* and vfio globs, and the GCE metadata server — no TPU (or network) required."""

import sys
import types

import pytest

import ray_tpu.accelerators.tpu as tpu_mod
from ray_tpu.accelerators.tpu import (
    TPU_CHIPS_PER_HOST_BOUNDS_ENV, TPU_HOST_BOUNDS_ENV,
    TPU_VISIBLE_CHIPS_ENV, TPUAcceleratorManager, pod_head_resource,
)


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for var in ("RAY_TPU_FAKE_CHIPS", "RAY_TPU_FAKE_POD_TYPE",
                "RAY_TPU_FAKE_POD_NAME", "RAY_TPU_FAKE_WORKER_ID",
                TPU_VISIBLE_CHIPS_ENV, TPU_CHIPS_PER_HOST_BOUNDS_ENV,
                TPU_HOST_BOUNDS_ENV):
        monkeypatch.delenv(var, raising=False)
    yield


def _mock_globs(monkeypatch, accel=(), vfio=()):
    def fake_glob(pattern):
        if pattern.startswith("/dev/accel"):
            return list(accel)
        if pattern.startswith("/dev/vfio"):
            return list(vfio)
        return []
    monkeypatch.setattr(tpu_mod.glob, "glob", fake_glob)


def _mock_metadata(monkeypatch, table):
    monkeypatch.setattr(tpu_mod, "_gce_metadata",
                        lambda path: table.get(path))


# ------------------------------------------------------------- detection

def test_chip_count_env_fake(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FAKE_CHIPS", "4")
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 4


def test_chip_count_dev_accel(monkeypatch):
    _mock_globs(monkeypatch,
                accel=[f"/dev/accel{i}" for i in range(4)])
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 4


def test_chip_count_vfio(monkeypatch):
    # Newer TPU-VM images expose vfio devices instead of /dev/accel*.
    _mock_globs(monkeypatch, accel=[],
                vfio=["/dev/vfio/0", "/dev/vfio/1"])
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 2


def test_chip_count_jax_enumeration(monkeypatch):
    """Node detection never asks JAX: a device query opens the TPU
    backend, and the asking process (driver, raylet) would then hold
    the chip a leased worker needs. Even an imported jax that reports
    TPU devices is left alone."""
    _mock_globs(monkeypatch)

    def devices():
        raise AssertionError("node detection must not query jax devices")

    fake_jax = types.SimpleNamespace(devices=devices)
    monkeypatch.setitem(sys.modules, "jax", fake_jax)
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 0


def test_chip_count_nothing_found(monkeypatch):
    _mock_globs(monkeypatch)
    monkeypatch.setitem(sys.modules, "jax", None)
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 0


def test_accelerator_type_from_metadata(monkeypatch):
    _mock_metadata(monkeypatch, {
        "instance/attributes/accelerator-type": "v5litepod-16"})
    assert (TPUAcceleratorManager.get_current_node_accelerator_type()
            == "v5litepod-16")


def test_accelerator_type_absent(monkeypatch):
    _mock_metadata(monkeypatch, {})
    assert TPUAcceleratorManager.get_current_node_accelerator_type() is None


def test_pod_name_and_worker_count(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FAKE_CHIPS", "4")
    _mock_metadata(monkeypatch, {
        "instance/attributes/accelerator-type": "v5e-16",
        "instance/attributes/instance-id": "my-slice-abc",
    })
    assert TPUAcceleratorManager.get_current_pod_name() == "my-slice-abc"
    # 16 chips / 4 per host = 4 workers.
    assert TPUAcceleratorManager.get_current_pod_worker_count() == 4


# ------------------------------------------------------- request quantity

@pytest.mark.parametrize("qty", [1, 2, 4, 0, 0.5])
def test_valid_chip_requests(qty):
    ok, err = TPUAcceleratorManager.validate_resource_request_quantity(qty)
    assert ok, err


@pytest.mark.parametrize("qty", [3, 5, 8, 1.5])
def test_invalid_chip_requests(qty):
    ok, err = TPUAcceleratorManager.validate_resource_request_quantity(qty)
    assert not ok
    assert err


# ------------------------------------------------------- visibility envs

def test_visible_chips_single(monkeypatch):
    import os

    TPUAcceleratorManager.set_current_process_visible_accelerator_ids(["0"])
    assert os.environ[TPU_VISIBLE_CHIPS_ENV] == "0"
    # A 1-chip process must shrink host bounds (reference tpu.py:158).
    assert os.environ[TPU_CHIPS_PER_HOST_BOUNDS_ENV] == "1,1,1"
    assert os.environ[TPU_HOST_BOUNDS_ENV] == "1,1,1"


def test_visible_chips_pair(monkeypatch):
    import os

    TPUAcceleratorManager.set_current_process_visible_accelerator_ids(
        ["1", "2"])
    assert os.environ[TPU_VISIBLE_CHIPS_ENV] == "1,2"
    assert os.environ[TPU_CHIPS_PER_HOST_BOUNDS_ENV] == "1,2,1"


def test_visible_chips_full_host(monkeypatch):
    import os

    os.environ[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = "1,1,1"
    TPUAcceleratorManager.set_current_process_visible_accelerator_ids(
        ["0", "1", "2", "3"])
    assert os.environ[TPU_VISIBLE_CHIPS_ENV] == "0,1,2,3"
    # Full host: bounds unset so the runtime sees the whole topology.
    assert TPU_CHIPS_PER_HOST_BOUNDS_ENV not in os.environ


# ---------------------------------------------------------- pod resources

def test_pod_gang_resources_worker0(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FAKE_CHIPS", "4")
    _mock_metadata(monkeypatch, {
        "instance/attributes/accelerator-type": "v5e-16",
        "instance/attributes/instance-id": "slice-x",
        "instance/attributes/agent-worker-number": "0",
    })
    out = TPUAcceleratorManager.get_current_node_extra_resources()
    assert out["TPU-v5e"] == 4
    assert out["slice-x"] == 1
    assert out["TPU-v5e-16-head"] == 1  # exactly worker 0 carries the head


def test_pod_gang_resources_other_worker(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FAKE_CHIPS", "4")
    _mock_metadata(monkeypatch, {
        "instance/attributes/accelerator-type": "v5e-16",
        "instance/attributes/instance-id": "slice-x",
        "instance/attributes/agent-worker-number": "2",
    })
    out = TPUAcceleratorManager.get_current_node_extra_resources()
    assert out["TPU-v5e"] == 4
    assert "TPU-v5e-16-head" not in out


def test_pod_gang_resources_no_metadata(monkeypatch):
    _mock_metadata(monkeypatch, {})
    assert TPUAcceleratorManager.get_current_node_extra_resources() == {}


def test_pod_head_resource_helper():
    assert pod_head_resource("v5e-16") == {"TPU-v5e-16-head": 1}


def test_accel_version_parsing():
    assert tpu_mod._accel_version("v5litepod-16") == "v5litepod"
    assert tpu_mod._accel_version("v4-8") == "v4"
    assert tpu_mod._accel_version("weird") is None
    assert tpu_mod._pod_chip_count("v5e-16") == 16
    assert tpu_mod._pod_chip_count("nope") is None
