"""The loud paths: nothing on the serving path may make a CPU run look
like a chip run, hand a mesh another platform's devices, move the
compile cache, or start a chip-owning child from a chip-owning parent.
"""

import os

import pytest

from omero_ms_image_region_tpu.utils import jaxenv


# ------------------------------------------------------ compile cache

@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test that placed it."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_from_environment_sets_nothing_in_code(monkeypatch,
                                                     cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = cache_config.jax_compilation_cache_dir
    assert jaxenv.place_compilation_cache("/configured") == "/some/dir"
    assert cache_config.jax_compilation_cache_dir == before


def test_cache_defaults_to_the_fixed_checkout_path(monkeypatch,
                                                   cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert jaxenv.CHECKOUT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert jaxenv.place_compilation_cache() == jaxenv.CHECKOUT_CACHE_DIR
    assert cache_config.jax_compilation_cache_dir \
        == jaxenv.CHECKOUT_CACHE_DIR
    # Same answer every time: the directory is part of the cache key.
    assert jaxenv.place_compilation_cache() == jaxenv.CHECKOUT_CACHE_DIR


def test_cache_configured_path_beats_the_default(monkeypatch,
                                                 cache_config, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jaxenv.place_compilation_cache(str(tmp_path)) == str(tmp_path)
    assert cache_config.jax_compilation_cache_dir == str(tmp_path)


def test_no_file_sets_the_cache_dir_but_the_helper():
    """One helper: no other module, script or bench calls
    ``jax.config.update("jax_compilation_cache_dir", ...)``."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    offenders = []
    for root, dirs, files in os.walk(repo):
        dirs[:] = [d for d in dirs if d not in (".git", "tests",
                                                "chiprun_out")
                   and not d.startswith(".")]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                if '"jax_compilation_cache_dir"' in f.read() \
                        and not path.endswith("utils/jaxenv.py"):
                    offenders.append(os.path.relpath(path, repo))
    assert offenders == []


# ------------------------------------------------- device identity

def test_device_identity_names_what_jax_reports():
    import jax
    ident = jaxenv.device_identity()      # conftest asks for the CPU
    assert ident == {"platform": "cpu",
                     "kind": jax.devices()[0].device_kind,
                     "count": len(jax.devices()),
                     "ids": [d.id for d in jax.devices()]}
    two = jaxenv.device_identity(jax.devices()[:2])
    assert two["count"] == 2 and two["ids"] == [0, 1]


@pytest.mark.parametrize("platforms", [None, "", "tpu", "tpu,cpu"])
def test_cpu_backend_nobody_asked_for_is_refused(monkeypatch, platforms):
    """No accelerator found (JAX fell back to the CPU) and JAX_PLATFORMS
    does not ask for the CPU: a start-up error."""
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(RuntimeError, match="no accelerator found"):
        jaxenv.device_identity()


def test_cpu_by_name_is_served(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", " CPU,tpu")
    assert jaxenv.device_identity()["platform"] == "cpu"


def test_device_owning_role_refuses_to_start_without_accelerator(
        monkeypatch, tmp_path):
    """Through the entry both device-owning roles share: the combined
    app and the sidecar build their stack with ``build_services``."""
    from omero_ms_image_region_tpu.server.app import build_services
    from omero_ms_image_region_tpu.server.config import AppConfig
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(RuntimeError, match="no accelerator found"):
        build_services(AppConfig(data_dir=str(tmp_path)))


def test_build_services_carries_device_and_native(monkeypatch, tmp_path):
    from omero_ms_image_region_tpu.server.app import build_services
    from omero_ms_image_region_tpu.server.config import AppConfig
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    services = build_services(AppConfig(data_dir=str(tmp_path)))
    try:
        assert services.device["platform"] == "cpu"
        assert set(services.device) == {"platform", "kind", "count",
                                        "ids"}
        assert set(services.native) == {"entropy_coder", "tile_cache"}
    finally:
        services.pixels_service.close()


# ---------------------------------------------------------- meshes

def test_resolve_devices_raises_when_the_platform_is_too_small():
    import jax
    from omero_ms_image_region_tpu.parallel.mesh import (make_mesh,
                                                         resolve_devices)
    have = len(jax.devices())
    assert len(resolve_devices(have)) == have
    with pytest.raises(ValueError, match=f"only {have} device"):
        resolve_devices(have + 1)
    with pytest.raises(ValueError, match=f"only {have} device"):
        make_mesh(have + 1)
    # Handing the devices over is the one way to pick them.
    mesh = make_mesh(2, devices=jax.devices("cpu")[:2])
    assert mesh.devices.size == 2


# ------------------------------------------------ one process per chip

@pytest.fixture
def holding_a_chip(monkeypatch):
    """Simulate a process whose JAX backend is an accelerator."""
    import jax
    jax.devices()                         # initialise (CPU, really)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_cpu_parent_may_spawn(monkeypatch):
    jaxenv.require_chip_free("test")      # CPU holds nothing: no raise


def test_chip_owning_parent_may_not_spawn_a_sidecar(holding_a_chip,
                                                    tmp_path):
    from omero_ms_image_region_tpu.server.sidecar import (
        SidecarSupervisor, spawn_sidecar)
    with pytest.raises(RuntimeError, match="belongs to one process"):
        spawn_sidecar(None, str(tmp_path / "never.sock"))
    spawned = []
    sup = SidecarSupervisor(lambda: spawned.append(1))
    with pytest.raises(RuntimeError, match="belongs to one process"):
        sup.start()
    assert spawned == []


@pytest.mark.parametrize("drill", ["bench_federation_smoke",
                                   "bench_partition_smoke",
                                   "bench_restart_smoke",
                                   "bench_offload_smoke"])
def test_cpu_contract_drills_refuse_an_accelerator(holding_a_chip, drill):
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench
    with pytest.raises(RuntimeError, match="CPU contract drill"):
        getattr(bench, drill)()


def test_cpu_contract_drill_pins_its_children(monkeypatch):
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    bench._cpu_contract_drill("--test")
    assert os.environ["JAX_PLATFORMS"] == "cpu"   # children inherit
