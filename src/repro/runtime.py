"""Process-level JAX set-up shared by the entry points.

* :func:`use_compile_cache` — where JAX keeps compiled programs between
  processes.  Every entry point calls it first.
* :func:`worker_envs` — the environment of each worker process a launcher
  starts: a TPU chip belongs to one process at a time, so workers that run
  JAX computations get a chip each and the rest stay off the chips.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, List, Optional

#: the repository checkout this module runs from
CHECKOUT = Path(__file__).resolve().parents[2]
#: the compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset.
#: Fixed on purpose: the path is part of what a later process must find,
#: so it is never built from a temporary name, a pid or the time.
COMPILE_CACHE_DIR = CHECKOUT / ".jax_cache"

#: first libtpu slice-builder port of pinned workers (libtpu's default);
#: worker ``i`` binds ``+ i`` so one-chip processes never collide
_TPU_PORT_BASE = 8476


def use_compile_cache() -> Optional[Path]:
    """Turn on JAX's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing (returns None).  Otherwise the cache lives at
    ``<checkout>/.jax_cache`` (returned).  Touches no backend, so a
    launcher may call it before it spawns workers."""
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return None
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return COMPILE_CACHE_DIR


def local_tpu_chips(pci: Path = Path("/sys/bus/pci/devices"),
                    dev: Path = Path("/dev")) -> int:
    """TPU chips this process can open, counted without initialising a
    JAX backend, which would claim every chip for the caller and leave
    none for the workers it is about to start.

    A chip counts when it is a TPU on the PCI bus and its device node is
    present: ``/dev/accelN``, or ``/dev/vfio/<iommu group>`` on v5e and
    later.  A container can see all of its host's chips on the bus and
    be given the nodes of only some of them."""
    from jax._src.hardware_utils import (_GOOGLE_PCI_VENDOR_ID,
                                         _TPU_PCI_DEVICE_IDS)
    chips = 0
    for fn in pci.glob("*"):
        if ((fn / "vendor").read_text().strip() != _GOOGLE_PCI_VENDOR_ID
                or (fn / "device").read_text().strip()
                not in _TPU_PCI_DEVICE_IDS):
            continue
        nodes = [dev / a.name for a in (fn / "accel").glob("accel*")]
        if (fn / "iommu_group").exists():
            nodes.append(dev / "vfio" / (fn / "iommu_group").resolve().name)
        chips += any(node.exists() for node in nodes)
    return chips


def host_chip(device) -> int:
    """The index on its host of the chip ``device`` is.  JAX numbers the
    chips a process sees from 0, so a worker pinned to one chip through
    ``TPU_VISIBLE_CHIPS`` (see :func:`worker_envs`) sees it as device 0."""
    visible = os.environ.get("TPU_VISIBLE_CHIPS", "")
    if device.platform == "tpu" and visible:
        return int(visible.split(",")[device.id])
    return device.id


def worker_envs(n: int, uses_device: bool,
                base: Optional[Dict[str, str]] = None) -> List[Dict[str, str]]:
    """One environment per worker process, from ``base`` (``os.environ``).

    Of the prediction workers only those that score trained MLPs run JAX
    computations; the fleet engine itself is NumPy.  Workers that do not
    (``uses_device=False``) get ``JAX_PLATFORMS=cpu``, so nothing they
    import can claim a chip.  Workers that do, on a host with TPU chips,
    are pinned to chip ``i`` each; ``ValueError`` when there are fewer
    chips than such workers.  ``ALLOW_MULTIPLE_LIBTPU_LOAD`` lets the
    pinned processes load libtpu side by side: the pinning, not libtpu's
    host-wide lock, is what keeps two of them off one chip."""
    base = dict(os.environ if base is None else base)
    if not uses_device:
        return [dict(base, JAX_PLATFORMS="cpu") for _ in range(n)]
    chips = 0 if base.get("JAX_PLATFORMS") == "cpu" else local_tpu_chips()
    if chips == 0:
        return [dict(base) for _ in range(n)]
    if n > chips:
        raise ValueError(
            f"{n} workers would score MLPs on the TPU, but this host has "
            f"{chips} chip(s) and each such worker needs one of its own; "
            f"start at most {chips}")
    # each worker is a one-process, one-chip slice: its own port is the
    # only address of that slice
    return [dict(base,
                 TPU_VISIBLE_CHIPS=str(i),
                 TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                 TPU_PROCESS_BOUNDS="1,1,1",
                 TPU_PROCESS_PORT=str(_TPU_PORT_BASE + i),
                 TPU_PROCESS_ADDRESSES=f"localhost:{_TPU_PORT_BASE + i}",
                 ALLOW_MULTIPLE_LIBTPU_LOAD="1")
            for i in range(n)]
