"""Production mesh factory (a FUNCTION, never module-level state — importing
this module must not touch jax device state).

Target: TPU v5e pods; 256 chips/pod as a (16, 16) (data, model) torus;
multi-pod adds a leading "pod" axis (pure DP across the slow inter-pod
links).  Hardware constants used by the roofline layer live here too.
"""
from __future__ import annotations

# TPU v5e per-chip peaks, from Google Cloud's "TPU v5e" documentation
# (bf16 peak, HBM bandwidth; ICI_BW is 1,600 Gbit/s over 4 links)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    from ..jaxcompat import make_mesh
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(multi_pod: bool) -> tuple[str, ...]:
    """Axes that carry batch/FSDP sharding ('pod' folds into data)."""
    return ("pod", "data") if multi_pod else ("data",)


def n_devices(multi_pod: bool) -> int:
    return 512 if multi_pod else 256
