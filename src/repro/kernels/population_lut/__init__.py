"""Population LUT-gather: the batched behavioral sim's inner gather as an
XLA gather (the fused engine's path on every backend), a tiled Pallas
kernel (interpret mode only) and a numpy reference.

``out[g, m, s] = lut[genes[g, s], s, cols[m, s]]`` — one gathered
product per (genome, input element, multiplier slot), the population
analogue of ``accel._batchsim.lut_gather``.
"""

from .kernel import population_lut_gather_pallas
from .ops import gather_xla, population_lut_gather
from .ref import population_lut_gather_ref

__all__ = [
    "population_lut_gather",
    "population_lut_gather_pallas",
    "population_lut_gather_ref",
    "gather_xla",
]
