"""Launchers: mesh construction, multi-pod dry-run, training and serving CLIs.

NOTE: ``repro.launch.dryrun`` sets ``XLA_FLAGS`` at import (512 placeholder
host devices) — never import it from library code or tests; invoke it as
``python -m repro.launch.dryrun``.
"""
from repro.launch.cache import compile_cache
from repro.launch.mesh import make_debug_mesh, make_engine_mesh, make_production_mesh

__all__ = [
    "compile_cache",
    "make_debug_mesh",
    "make_engine_mesh",
    "make_production_mesh",
]
