"""Launch-layer helpers: host meshes, the compile cache's location, and the
device-kind to hardware-spec map the serving tuner files results under."""
import types

import jax
import pytest
from jax.sharding import AxisType

from repro.core import hwspec
from repro.launch import cache
from repro.launch.mesh import make_host_mesh


def test_host_mesh_uses_every_device_with_auto_axes():
    n = len(jax.devices())
    mesh = make_host_mesh(n, 1)
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": n, "model": 1}
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


@pytest.mark.parametrize("data,model", [(2, 1), (1, 2)])
def test_host_mesh_refuses_a_shape_it_cannot_fill(data, model):
    n = len(jax.devices())
    with pytest.raises(ValueError, match="needs"):
        make_host_mesh(data * n, model)


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_compile_cache_defaults_to_the_checkout(monkeypatch, tmp_path,
                                                cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cache, "CHECKOUT_CACHE_DIR", tmp_path / ".jax_cache")
    assert cache.enable_compile_cache() == str(tmp_path / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / ".jax_cache")


def test_compile_cache_keeps_the_environments_dir(monkeypatch, tmp_path,
                                                  cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    monkeypatch.setattr(cache, "CHECKOUT_CACHE_DIR", tmp_path / ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / ".jax_cache").exists()


@pytest.mark.parametrize("kind,name", sorted(hwspec.DEVICE_KINDS.items()))
def test_device_kind_maps_to_its_spec(kind, name):
    dev = types.SimpleNamespace(device_kind=kind)
    assert hwspec.spec_for_device(dev) is hwspec.SPECS[name]


def test_unregistered_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no hardware spec"):
        hwspec.spec_for_device(types.SimpleNamespace(device_kind="cpu"))
