from . import hlo, roofline

__all__ = ["hlo", "roofline"]
