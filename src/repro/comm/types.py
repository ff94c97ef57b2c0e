"""Communication types and hardware model constants.

``CommunicationType`` is the paper's Fig. 1 selector: every distributed
primitive in :mod:`repro.comm.collectives` has one implementation per type,
and benchmarks/trainers pick the implementation at run time — exactly the
paper's ``ExecutionImplementation`` architecture.

``HardwareModel`` carries the constants for the analytical performance models
(paper Eqs. 2-6) and the roofline terms. Defaults are TPU v5e (the assigned
target), with the paper's BittWare 520N given for cross-checking the
reproduction against the paper's own numbers.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass


class CommunicationType(enum.Enum):
    # Direct device-to-device over the circuit-switched interconnect
    # (paper: Intel External Channels / CSN; here: TPU ICI).
    ICI_DIRECT = "ici_direct"
    # Staged through the hosts (paper: PCIe + MPI over the inter-CPU network;
    # here: DCN across pods / store-and-forward emulation intra-pod).
    HOST_STAGED = "host_staged"


def comm_type(name) -> CommunicationType:
    if isinstance(name, CommunicationType):
        return name
    return CommunicationType(name)


@dataclass(frozen=True)
class HardwareModel:
    name: str
    peak_flops: float          # peak matmul FLOP/s per chip (bf16 for v5e)
    hbm_bw: float              # HBM bytes/s per chip
    ici_link_bw: float         # bytes/s per ICI link (per direction)
    ici_links: int             # torus links per chip
    ici_latency: float         # seconds per hop
    pcie_bw: float             # bytes/s device<->host
    dcn_bw: float              # bytes/s per host over the data-center network
    mpi_latency: float         # host-network small-message latency (s)
    vmem_bytes: int = 0        # per-core fast memory (VMEM / BRAM analogue)
    hbm_bytes: int = 0


TPU_V5E = HardwareModel(
    name="tpu_v5e",
    peak_flops=197e12,         # bf16
    hbm_bw=819e9,
    ici_link_bw=50e9,          # ~50 GB/s per link per assignment
    ici_links=4,               # 2-D torus
    ici_latency=1e-6,
    pcie_bw=15.75e9,           # PCIe 4.0 x8 host staging
    dcn_bw=25e9,
    mpi_latency=10e-6,
    vmem_bytes=16 * 2**20,
    hbm_bytes=16 * 2**30,
)

# Published peaks keyed by ``jax.devices()[0].device_kind`` ("TPU v5 lite"
# is how JAX names a v5e). Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s). A device missing here is an
# error for any measurement, never a default.
PEAKS = {"TPU v5 lite": TPU_V5E}


# The paper's evaluation hardware, for validating the reproduction's
# analytical models against the paper's own measurements (Fig. 10, Eq. 4).
BITTWARE_520N = HardwareModel(
    name="bittware_520n",
    peak_flops=8.6e12,         # fp32 DSP peak-ish (not used by models)
    hbm_bw=76.8e9,             # 4x DDR4 banks, 19.2 GB/s each
    ici_link_bw=5e9,           # 40 Gbit/s serial channel
    ici_links=4,
    ici_latency=520e-9,        # Table 2: c_l
    pcie_bw=7.88e9,            # PCIe 3.0 x8
    dcn_bw=12.5e9,             # Omni-Path 100 Gbit/s
    mpi_latency=1.5e-6,
)

# External-channel IP parameters of the 520N (paper Table 2) for Eq. 3/4.
CHANNEL_FREQ_520N = 156.25e6   # c_f
CHANNEL_WIDTH_520N = 32        # c_w bytes
CHANNELS_520N = 4              # c_n
