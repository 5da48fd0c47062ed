from . import sharding

__all__ = ["sharding"]
