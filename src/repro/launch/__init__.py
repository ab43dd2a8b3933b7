"""Launchers: production mesh, train driver, HLO analysis, replica worker."""
from .mesh import make_host_mesh, make_production_mesh

__all__ = ["make_host_mesh", "make_production_mesh"]
