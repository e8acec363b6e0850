"""Tests for the runtime layer (mesh, process identity, rank-0 convention)."""

import jax
import numpy as np
import pytest

from tpu_syncbn import runtime


def test_eight_fake_devices():
    assert jax.device_count() == 8


def test_initialize_single_host_noop():
    runtime.initialize()
    assert runtime.is_initialized()
    assert runtime.process_count() == 1
    assert runtime.process_index() == 0
    assert runtime.global_device_count() == 8


def test_data_parallel_mesh_spans_all_devices():
    mesh = runtime.data_parallel_mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.devices.size == 8


def test_data_parallel_mesh_subset():
    mesh = runtime.data_parallel_mesh(num_replicas=2)
    assert mesh.devices.size == 2
    with pytest.raises(ValueError):
        runtime.data_parallel_mesh(num_replicas=1000)


def test_make_mesh_wildcard_and_multi_axis():
    mesh = runtime.make_mesh({"data": -1, "model": 2})
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {"data": 4, "model": 2}
    with pytest.raises(ValueError):
        runtime.make_mesh({"data": 3})  # 8 not divisible
    with pytest.raises(ValueError):
        runtime.make_mesh({"a": -1, "b": -1})


def test_master_conventions(capsys):
    assert runtime.is_master()
    runtime.master_print("hello from master")
    assert "hello from master" in capsys.readouterr().out


def test_barrier_completes():
    runtime.barrier()


def test_logger_master_level():
    logger = runtime.get_logger()
    assert logger.level in (10, 20)  # INFO on master


def test_logger_stream_env_knob(monkeypatch):
    """TPU_SYNCBN_LOG_STREAM=stderr reroutes a freshly created package
    logger off stdout, for callers whose stdout is a parsed result
    channel."""
    import sys

    monkeypatch.setenv("TPU_SYNCBN_LOG_STREAM", "stderr")
    lg = runtime.get_logger("tpu_syncbn.test_stream_knob")
    assert lg.handlers[0].stream is sys.stderr
    monkeypatch.delenv("TPU_SYNCBN_LOG_STREAM")
    lg2 = runtime.get_logger("tpu_syncbn.test_stream_knob_default")
    assert lg2.handlers[0].stream is sys.stdout
