"""Calibrated storage/interconnect timing model (paper hardware envelope).

The host has no NVMe SSD array or PCIe switches, so the IO engines price
their reads and writes with the paper's hardware characteristics over the
memory-mapped storage tier: per-SSD sequential bandwidth and IOPS
ceilings (Intel P5510-class), PCIe 4.0x16 host<->device bandwidth, and
HBM-class cache bandwidth.  The model is *deterministic* given a request
trace.

Times are virtual seconds, the modelled cost of hardware the host does
not have; the engines and the cache report them as such, beside the work
they really do.  The serving loop also schedules on a ``VirtualClock``
with the operator constants below.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

from repro.ft.chaos import ChaosSchedule, FaultDecision


@dataclass(frozen=True)
class HardwareEnvelope:
    # Intel P5510-class NVMe (paper: 12x 3.84TB)
    ssd_seq_bw: float = 6.5e9          # bytes/s sequential read per SSD
    ssd_4k_iops: float = 700e3         # 4KiB random read IOPS per SSD
    ssd_seq_write_bw: float = 3.4e9    # bytes/s sequential write per SSD
    ssd_4k_write_iops: float = 200e3   # 4KiB random write IOPS per SSD
    ssd_min_io: int = 512              # bytes, min access granularity
    ssd_latency: float = 90e-6         # seconds, per-IO latency
    nvme_queue_depth: int = 1024       # per SSD
    # PCIe 4.0 x16 (GPU <-> host / switch)
    pcie_bw: float = 21.5e9            # effective bytes/s (paper ~20 GiB/s)
    # device memory (A100-class in paper; v5e HBM on target)
    hbm_bw: float = 1.6e12             # bytes/s usable
    # host memory
    dram_bw: float = 80e9              # bytes/s effective random-gather


DEFAULT_ENVELOPE = HardwareEnvelope()

# Calibrated operator cost constants of the inference server's virtual
# schedule.
SAMPLE_RATE_DEVICE = 2e9       # bytes/s of edge data, device-managed sampling
SAMPLE_RATE_CPU = 0.04e9       # CPU-managed sampling+batch build (paper I1)
MATMUL_RATE = 60e12            # flops/s device matmul throughput
HOST_STAGE_BW = 2e9            # bytes/s CPU staging-buffer gather


@dataclass
class SSDModel:
    """Throughput/latency model for one SSD under concurrent NVMe commands.

    ``chaos`` attaches a seeded fault schedule: the engines consult
    ``fault()`` on every per-shard service attempt, so injected media
    errors, latency spikes, stuck windows, and torn writes are part of
    the *hardware model*, deterministic given the request trace."""
    env: HardwareEnvelope = field(default_factory=lambda: DEFAULT_ENVELOPE)
    chaos: ChaosSchedule | None = None

    def fault(self, stream: int, kind: str, seq: int,
              attempt: int) -> FaultDecision | None:
        """Schedule-driven fault for one service attempt (None = clean)."""
        if self.chaos is None:
            return None
        return self.chaos.decide(stream, kind, seq, attempt)

    def io_time(self, n_requests: int, bytes_per_request: int,
                queue_depth: int) -> float:
        """Virtual seconds to complete n random reads of the given size with
        ``queue_depth`` concurrent commands in flight."""
        if n_requests == 0:
            return 0.0
        size = max(bytes_per_request, self.env.ssd_min_io)
        # effective IOPS ceiling: device IOPS limit and sequential-bw limit
        max_iops = min(self.env.ssd_4k_iops, self.env.ssd_seq_bw / size)
        # Little's law: ~256 in-flight commands saturate one device
        qd_frac = min(1.0, queue_depth / 256.0)
        iops = max_iops * qd_frac
        service = n_requests / max(iops, 1.0)
        return self.env.ssd_latency + service

    def range_io_time(self, n_ranges: int, total_bytes: int,
                      queue_depth: int) -> float:
        """Virtual seconds for ``n_ranges`` SEQUENTIAL range reads totalling
        ``total_bytes`` (coalesced row runs, gap waste included): each range
        costs one command issue on the IOPS path, and the payload streams at
        sequential bandwidth.  A fully-uncoalesced batch (every range a
        single row) degenerates to ~the 4K-random cost; dense runs approach
        the sequential-bandwidth ceiling instead of the IOPS ceiling."""
        if n_ranges == 0:
            return 0.0
        nbytes = max(total_bytes, n_ranges * self.env.ssd_min_io)
        qd_frac = min(1.0, queue_depth / 256.0)
        iops = self.env.ssd_4k_iops * qd_frac
        t_cmd = n_ranges / max(iops, 1.0)
        t_stream = nbytes / self.env.ssd_seq_bw
        return self.env.ssd_latency + t_cmd + t_stream

    def write_io_time(self, n_requests: int, bytes_per_request: int,
                      queue_depth: int) -> float:
        """Virtual seconds for n random WRITES: same queue-depth/Little's-law
        shape as ``io_time`` but against the (lower) write ceilings — NAND
        program cost makes small random writes ~3.5x slower than reads."""
        if n_requests == 0:
            return 0.0
        size = max(bytes_per_request, self.env.ssd_min_io)
        max_iops = min(self.env.ssd_4k_write_iops,
                       self.env.ssd_seq_write_bw / size)
        qd_frac = min(1.0, queue_depth / 256.0)
        service = n_requests / max(max_iops * qd_frac, 1.0)
        return self.env.ssd_latency + service

    def range_write_time(self, n_ranges: int, total_bytes: int,
                         queue_depth: int) -> float:
        """Virtual seconds for ``n_ranges`` SEQUENTIAL range writes totalling
        ``total_bytes``: one command issue per range on the write-IOPS path,
        payload streamed at sequential WRITE bandwidth.  Coalesced dirty-row
        runs approach the sequential-write ceiling instead of the random
        write-IOPS ceiling — the same lever as ``range_io_time``, applied to
        the flush path."""
        if n_ranges == 0:
            return 0.0
        nbytes = max(total_bytes, n_ranges * self.env.ssd_min_io)
        qd_frac = min(1.0, queue_depth / 256.0)
        iops = self.env.ssd_4k_write_iops * qd_frac
        t_cmd = n_ranges / max(iops, 1.0)
        t_stream = nbytes / self.env.ssd_seq_write_bw
        return self.env.ssd_latency + t_cmd + t_stream


@dataclass
class ArrayModel:
    """N SSDs striped; requests round-robin across submission queues."""
    n_ssds: int = 12
    env: HardwareEnvelope = field(default_factory=lambda: DEFAULT_ENVELOPE)

    def read_time(self, n_requests: int, bytes_per_request: int,
                  queue_depth_total: int) -> float:
        ssd = SSDModel(self.env)
        per = math.ceil(n_requests / max(self.n_ssds, 1))
        t_ssd = ssd.io_time(per, bytes_per_request,
                            queue_depth_total // max(self.n_ssds, 1))
        # transfers also cross PCIe (bounded by link bw)
        t_pcie = (n_requests * max(bytes_per_request, self.env.ssd_min_io)
                  / self.env.pcie_bw)
        return max(t_ssd, t_pcie)

    def write_time(self, n_requests: int, bytes_per_request: int,
                   queue_depth_total: int) -> float:
        """Random-write mirror of ``read_time``: requests stripe round-robin
        over the array's submission queues, payload crosses PCIe host->SSD."""
        ssd = SSDModel(self.env)
        per = math.ceil(n_requests / max(self.n_ssds, 1))
        t_ssd = ssd.write_io_time(per, bytes_per_request,
                                  queue_depth_total // max(self.n_ssds, 1))
        t_pcie = n_requests * max(bytes_per_request,
                                  self.env.ssd_min_io) / self.env.pcie_bw
        return max(t_ssd, t_pcie)

    def peak_bw(self, bytes_per_request: int) -> float:
        """Achievable aggregate read bandwidth (bytes/s) at full queue depth."""
        size = max(bytes_per_request, self.env.ssd_min_io)
        per_ssd = min(self.env.ssd_seq_bw, self.env.ssd_4k_iops * size)
        return min(per_ssd * self.n_ssds, self.env.pcie_bw)


@dataclass(frozen=True)
class NetworkEnvelope:
    """Simulated datacenter fabric between workers (100GbE-class RoCE)."""
    latency: float = 15e-6             # seconds, one-way message latency
    bandwidth: float = 11.0e9          # bytes/s effective per-link payload
    msg_overhead: float = 1.2e-6       # seconds per message (framing/doorbell)
    max_inflight: int = 64             # messages pipelined per link


DEFAULT_NETWORK = NetworkEnvelope()


@dataclass
class NetworkModel:
    """Latency/bandwidth/message-overhead model for one peer link.

    Sibling of ``SSDModel``: the remote tier prices a gather as one
    round-trip plus per-message command overhead plus the payload streamed
    at link bandwidth.  Messages pipeline up to ``max_inflight`` so a batch
    pays the wire latency once, not per message — the same Little's-law
    shape as the NVMe queue-depth fraction.

    ``chaos`` mirrors ``SSDModel.chaos`` for the fabric: per-peer
    transient drops, latency-spike and frozen-peer windows consulted by
    ``RemoteIOEngine`` on every peer service attempt.
    """
    net: NetworkEnvelope = field(default_factory=lambda: DEFAULT_NETWORK)
    chaos: ChaosSchedule | None = None

    def fault(self, stream: int, kind: str, seq: int,
              attempt: int) -> FaultDecision | None:
        """Schedule-driven fault for one peer service attempt."""
        if self.chaos is None:
            return None
        return self.chaos.decide(stream, kind, seq, attempt)

    def xfer_time(self, n_messages: int, total_bytes: int) -> float:
        """Virtual seconds to move ``total_bytes`` split over
        ``n_messages`` request/response messages across the link."""
        if n_messages == 0:
            return 0.0
        pipeline_frac = min(1.0, self.net.max_inflight / max(n_messages, 1))
        lat = self.net.latency * (2.0 - pipeline_frac)  # rtt amortised
        t_msg = n_messages * self.net.msg_overhead
        t_stream = total_bytes / self.net.bandwidth
        return lat + t_msg + t_stream

    def gather_time(self, n_rows: int, row_bytes: int,
                    n_peers: int = 1) -> float:
        """Virtual seconds for a batched remote gather of ``n_rows`` rows
        fanned out over ``n_peers`` links in parallel (bounded by the
        slowest peer; rows assumed evenly spread)."""
        if n_rows == 0 or n_peers <= 0:
            return 0.0
        per = math.ceil(n_rows / n_peers)
        return self.xfer_time(per, per * row_bytes)


def pcie_time(nbytes: float, env: HardwareEnvelope = DEFAULT_ENVELOPE) -> float:
    return nbytes / env.pcie_bw


def dram_gather_time(nbytes: float, env: HardwareEnvelope = DEFAULT_ENVELOPE) -> float:
    return nbytes / env.dram_bw


def hbm_gather_time(nbytes: float, env: HardwareEnvelope = DEFAULT_ENVELOPE) -> float:
    return nbytes / env.hbm_bw


@dataclass
class VirtualClock:
    """Tracks overlap-aware virtual time across pipeline resources."""
    resources: dict = field(default_factory=dict)   # name -> busy-until

    def schedule(self, resource: str, start: float, duration: float) -> float:
        """Schedule work on a serial resource; returns completion time."""
        free_at = self.resources.get(resource, 0.0)
        begin = max(start, free_at)
        end = begin + duration
        self.resources[resource] = end
        return end

    def busy_until(self, resource: str) -> float:
        """Virtual time the resource frees up (0.0 if never scheduled)."""
        return self.resources.get(resource, 0.0)

    def makespan(self) -> float:
        """Completion time of the LAST scheduled work across every
        resource — the end-to-end virtual time of an overlapped schedule
        (what the split-phase write benchmark compares against the serial
        compute+write sum)."""
        return max(self.resources.values(), default=0.0)
