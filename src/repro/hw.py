"""Target-hardware constants (TPU v5e) shared by roofline + perfsim.

These are the numbers mandated for the roofline analysis:
197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class HWSpec:
    device_kind: str = "TPU v5 lite"  # jax.devices()[0].device_kind of a v5e
    peak_flops: float = 197e12        # bf16 FLOP/s per chip
    hbm_bw: float = 819e9             # bytes/s per chip
    ici_bw: float = 50e9              # bytes/s per link per direction
    ici_links: int = 1                # roofline term uses chips × link_bw
    hop_latency: float = 1e-6         # per collective-permute hop (s)
    vmem_bytes: int = 128 * 1024**2   # v5e VMEM per core (staging budget ref)
    hbm_bytes: int = 16 * 1024**3     # v5e HBM per chip
    # Inter-node tier (the slow ``tp_out`` axis of a hierarchical 2D-TP
    # mesh — docs/topology.md). Defaults model a DCN-attached pod slice:
    # ~12.5 GB/s/dir per host and tens of microseconds per hop.
    dcn_bw: float = 12.5e9            # bytes/s per link per direction
    dcn_latency: float = 25e-6        # per inter-node hop (s)

    def inter_tier(self) -> "HWSpec":
        """This spec with the ICI link terms replaced by the inter-node
        tier's, so α-β consumers (``coordination.plan``) can be pointed at
        the slow axis without growing a second code path."""
        from dataclasses import replace
        return replace(self, ici_bw=self.dcn_bw, hop_latency=self.dcn_latency)


V5E = HWSpec()
