"""TPU chip specifications used by the autotiler and the roofline analysis.

The numbers for the *target* chip (TPU v5e) follow the constants mandated for
this reproduction: 197 TFLOP/s bf16 per chip, 819 GB/s HBM bandwidth,
~50 GB/s per ICI link. VMEM sizes follow public documentation (order
128 MiB on recent chips); a configurable ``vmem_reserved_bytes`` models the
compiler-reserved scratch -- the TPU analogue of the paper's observation
(§4.4.2) that the *usable* TCL is below the nominal cache size because other
state competes for it.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import lru_cache

from repro.core.hierarchy import MemoryLevel, tpu_hierarchy


@dataclass(frozen=True)
class TPUSpec:
    name: str
    peak_bf16_flops: float          # FLOP/s per chip
    hbm_bytes: int
    hbm_bw: float                   # bytes/s
    vmem_bytes: int                 # per TensorCore
    vmem_reserved_bytes: int        # compiler scratch / semaphores / spills
    ici_bw_per_link: float          # bytes/s per link per direction
    ici_links_per_axis: int         # usable links along one torus axis
    num_cores: int                  # TensorCores per chip
    mxu: int = 128                  # systolic array dim
    sublane_bytes: int = 4 * 8      # granule: 8 sublanes of f32
    lane: int = 128

    @property
    def usable_vmem(self) -> int:
        return self.vmem_bytes - self.vmem_reserved_bytes

    def hierarchy(self, mesh_devices: int = 0, hosts: int = 1) -> MemoryLevel:
        """This chip in the paper's §3.1 JSON schema (HBM -> VMEM -> VREG);
        with ``mesh_devices`` the mesh-extended ICI -> HBM -> ... chain, and
        with ``hosts > 1`` the DCN level above it (``mesh_devices`` chips
        per host -- see ``tpu_hierarchy`` / DESIGN.md §6)."""
        return tpu_hierarchy(
            hbm_bytes=self.hbm_bytes,
            vmem_bytes=self.usable_vmem,
            lane_tile_bytes=self.sublane_bytes * self.lane,
            n_cores=self.num_cores,
            mesh_devices=mesh_devices,
            hosts=hosts,
        )

    def sublane(self, dtype_bytes: int) -> int:
        """Second-minor tile granule: 8 for f32, 16 for bf16, 32 for int8."""
        return max(8, (4 // max(1, dtype_bytes)) * 8)


# Target chip for this reproduction (constants per the assignment).
TPU_V5E = TPUSpec(
    name="tpu_v5e",
    peak_bf16_flops=197e12,
    hbm_bytes=16 << 30,
    hbm_bw=819e9,
    vmem_bytes=128 << 20,
    vmem_reserved_bytes=32 << 20,
    ici_bw_per_link=50e9,
    ici_links_per_axis=1,
    num_cores=1,
)

TPU_V4 = TPUSpec(
    name="tpu_v4",
    peak_bf16_flops=275e12,
    hbm_bytes=32 << 30,
    hbm_bw=1228e9,
    vmem_bytes=128 << 20,
    vmem_reserved_bytes=32 << 20,
    ici_bw_per_link=50e9,
    ici_links_per_axis=1,
    num_cores=2,   # megacore: the SRRC "sibling cores sharing an LLC(HBM)"
)

TPU_V5P = TPUSpec(
    name="tpu_v5p",
    peak_bf16_flops=459e12,
    hbm_bytes=96 << 30,
    hbm_bw=2765e9,
    vmem_bytes=128 << 20,
    vmem_reserved_bytes=32 << 20,
    ici_bw_per_link=50e9,
    ici_links_per_axis=3,
    num_cores=2,
)

_SPECS = {s.name: s for s in (TPU_V5E, TPU_V4, TPU_V5P)}

#: ``jax.devices()[0].device_kind`` -> spec name.
_KINDS = {
    "TPU v5 lite": "tpu_v5e",
    "TPU v5e": "tpu_v5e",
    "TPU v4": "tpu_v4",
    "TPU v5": "tpu_v5p",
    "TPU v5p": "tpu_v5p",
}


@lru_cache(maxsize=1)
def _attached_chip() -> TPUSpec:
    """The spec of the TPU this process runs on, its HBM capped at what the
    runtime will actually hand out (``memory_stats()["bytes_limit"]``, which
    is below the nominal size); off-TPU, the v5e target this reproduction
    plans for."""
    import jax

    if jax.default_backend() != "tpu":
        return TPU_V5E
    dev = jax.devices()[0]
    name = _KINDS.get(dev.device_kind)
    if name is None:
        raise ValueError(f"no chip spec for device kind {dev.device_kind!r}; "
                         f"known: {sorted(_KINDS)}")
    spec = _SPECS[name]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if limit:
        spec = replace(spec, hbm_bytes=min(spec.hbm_bytes, int(limit)))
    return spec


def chip_spec(name: str = "", **overrides) -> TPUSpec:
    """A chip's spec by name, or with no name the chip this process runs on
    (``_attached_chip``); ``overrides`` replace fields (tests shrink VMEM)."""
    spec = _SPECS[name] if name else _attached_chip()
    return replace(spec, **overrides) if overrides else spec
