from . import ops, ref
from .counter_scatter import counter_scatter_pallas
from .first_live_scan import first_live_scan

__all__ = ["ops", "ref", "first_live_scan", "counter_scatter_pallas"]
