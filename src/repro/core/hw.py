"""Hardware constants used by the mapper and the kernel blockings.

TPU v5e per chip (Google Cloud documentation, "TPU v5e"):
  * 197 TFLOP/s bf16 peak (MXU), 393 TOP/s int8
  * 16 GB HBM at 819 GB/s
  * 1,600 Gbit/s chip-to-chip interconnect (4 links of ~50 GB/s)
  * we tile for a 16 MiB working-set budget per kernel invocation.

The MXU is itself a 128x128 systolic array -- the natural "array shape" for
the paper's runtime model when reasoning about TPU GeMM mapping.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops: float          # FLOP/s at the compute dtype
    hbm_bw: float              # bytes/s
    ici_bw_per_link: float     # bytes/s per link
    ici_links: int             # usable links per chip (2-D torus: 4)
    hbm_bytes: float
    vmem_bytes: float
    mxu_shape: tuple[int, int]


TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    ici_bw_per_link=50e9,
    ici_links=4,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=128 * 1024**2,
    mxu_shape=(128, 128),
)


# Working-set budget a single pallas_call block set should stay under.
VMEM_TILE_BUDGET = 16 * 1024**2

def legal_block(dim: int, target: int, align: int) -> int:
    """Block extent along one array dim that Mosaic can tile.

    Mosaic takes a block whose last dim is a multiple of 128, whose
    second-to-last is a multiple of the dtype's sublane count (8 rows of
    32-bit, 16 of 16-bit), or either equal to the array dim.  This returns
    the whole dim when ``target`` covers it (no padding); otherwise
    ``target`` rounded down to a multiple of ``align`` (at least ``align``),
    and the caller pads the dim up to a multiple of the result."""
    if target >= dim:
        return dim
    b = max(align, target // align * align)
    return dim if b >= dim else b
