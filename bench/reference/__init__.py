"""Plain reference of the ESDP dispatch slot, independent of ``repro``."""
from .esdp import Reference

__all__ = ["Reference"]
