"""Process-level set-up: the compile-cache rule and the environments a
launcher gives its workers (``repro.runtime``)."""

import os
import subprocess
import sys

import jax
import pytest

from repro import runtime


@pytest.fixture
def cache_dir_config():
    """Restore JAX's compile-cache path after a test that sets it."""
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_leaves_a_set_env_var_alone(monkeypatch,
                                                  cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert runtime.use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.use_compile_cache()
    assert path == runtime.CHECKOUT / ".jax_cache"
    assert (runtime.CHECKOUT / "src" / "repro" / "runtime.py").exists()
    assert jax.config.jax_compilation_cache_dir == str(path)


def _pci_function(pci, name, vendor, device):
    fn = pci / name
    fn.mkdir(parents=True)
    (fn / "vendor").write_text(vendor + "\n")
    (fn / "device").write_text(device + "\n")
    return fn


def test_chips_count_only_with_their_device_node(tmp_path):
    """Every chip of the host is on the PCI bus; only those whose device
    node this process was given count."""
    pci, dev, groups = tmp_path / "pci", tmp_path / "dev", tmp_path / "groups"
    (dev / "vfio").mkdir(parents=True)
    for i in range(4):                                  # v5e: vfio groups
        fn = _pci_function(pci, f"0000:00:0{4 + i}.0", "0x1ae0", "0x0063")
        (groups / str(10 + i)).mkdir(parents=True)
        (fn / "iommu_group").symlink_to(groups / str(10 + i))
    (dev / "vfio" / "11").touch()                       # chip 1 given
    v4 = _pci_function(pci, "0000:00:09.0", "0x1ae0", "0x005e")
    (v4 / "accel" / "accel0").mkdir(parents=True)       # v4: accel node
    (dev / "accel0").touch()
    _pci_function(pci, "0000:00:0a.0", "0x1ae0", "0x0042")    # not a TPU
    _pci_function(pci, "0000:00:0b.0", "0x8086", "0x0063")    # not Google
    assert runtime.local_tpu_chips(pci, dev) == 2
    (dev / "vfio" / "12").touch()
    assert runtime.local_tpu_chips(pci, dev) == 3


def test_workers_off_the_device_never_see_a_chip(monkeypatch):
    monkeypatch.setattr(runtime, "local_tpu_chips", lambda: 4)
    envs = runtime.worker_envs(3, uses_device=False, base={"A": "1"})
    assert envs == [{"A": "1", "JAX_PLATFORMS": "cpu"}] * 3


def test_device_workers_get_a_chip_each(monkeypatch):
    monkeypatch.setattr(runtime, "local_tpu_chips", lambda: 4)
    envs = runtime.worker_envs(4, uses_device=True, base={})
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    assert all(e["TPU_PROCESS_ADDRESSES"] == f"localhost:{e['TPU_PROCESS_PORT']}"
               for e in envs)
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)


def test_more_device_workers_than_chips_is_refused(monkeypatch):
    monkeypatch.setattr(runtime, "local_tpu_chips", lambda: 1)
    with pytest.raises(ValueError, match="1 chip"):
        runtime.worker_envs(2, uses_device=True, base={})


@pytest.mark.parametrize("base", [{}, {"JAX_PLATFORMS": "cpu"}],
                         ids=["no-chips", "cpu-forced"])
def test_device_workers_without_chips_share_the_host(monkeypatch, base):
    monkeypatch.setattr(runtime, "local_tpu_chips", lambda: 0)
    assert runtime.worker_envs(2, uses_device=True, base=base) \
        == [base, base]


def test_launcher_spawns_before_any_backend():
    """The pool launcher imports, sets the cache and builds worker
    environments without initialising a JAX backend — one would claim
    every chip before its workers could."""
    code = (
        "import sys\n"
        "from jax._src import xla_bridge\n"
        "from repro import runtime\n"
        "from repro.launch import serve\n"
        "from repro.serve import http, router\n"
        "runtime.use_compile_cache()\n"
        "runtime.worker_envs(2, uses_device=True)\n"
        "sys.exit(1 if xla_bridge.backends_are_initialized() else 0)\n")
    env = dict(os.environ, PYTHONPATH=str(runtime.CHECKOUT / "src"),
               JAX_COMPILATION_CACHE_DIR="")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


class _Dev:
    def __init__(self, platform, id):
        self.platform, self.id = platform, id


@pytest.mark.parametrize("visible,dev,chip", [
    ("2", _Dev("tpu", 0), 2),           # a pinned worker's only chip
    ("1,3", _Dev("tpu", 1), 3),
    ("", _Dev("tpu", 3), 3),            # unpinned: JAX's id is the chip
    ("2", _Dev("cpu", 0), 0),           # the pin names TPU chips only
], ids=["pinned", "two-visible", "unpinned", "cpu"])
def test_host_chip_names_the_chip_a_pin_chose(monkeypatch, visible, dev, chip):
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", visible)
    assert runtime.host_chip(dev) == chip

