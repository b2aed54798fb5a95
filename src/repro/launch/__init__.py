"""Launchers and mesh builders.

New code should go through :class:`repro.session.Session` (or ``python
-m repro train/serve``); the modules here remain the mechanical layer
the session drives:

* :mod:`repro.launch.mesh` — production / reordered / planned meshes;
* :mod:`repro.launch.compile_cache` — where JAX's persistent
  compilation cache lives;
* :mod:`repro.launch.train`, :mod:`repro.launch.serve` — launcher
  internals (their ``python -m`` entry points are deprecated shims
  delegating to :mod:`repro.cli`);
* :mod:`repro.launch.hlo_analysis`, :mod:`repro.launch.specs`,
  :mod:`repro.launch.dryrun` — HLO collective accounting and dry-run
  lowering cells.

Submodules import lazily so ``import repro.launch`` never touches jax.
"""

from importlib import import_module

_SUBMODULES = ("compile_cache", "dryrun", "hlo_analysis", "mesh", "serve",
               "specs", "train")

__all__ = list(_SUBMODULES)


def __getattr__(name: str):
    if name in _SUBMODULES:
        module = import_module(f"{__name__}.{name}")
        globals()[name] = module
        return module
    raise AttributeError(f"module 'repro.launch' has no attribute {name!r}")
