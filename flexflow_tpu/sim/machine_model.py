"""Machine models: analytic cluster models feeding the strategy search.

Reference: src/runtime/machine_model.cc — SimpleMachineModel (v0, flat
inter-GPU/inter-node bandwidths, defaults at machine_model.cc:68-70),
EnhancedMachineModel (v1, config file with membus/UPI/NIC/PCIe/NVLink),
and the fork's NetworkedMachineModel (arbitrary topology matrix with
routed transfers, simulator.h:515-605, network.cc).

TPU-native redesign: `TpuPodModel` models what actually exists on a pod
slice — a per-axis ICI torus (per-hop bandwidth/latency, wraparound
links) and DCN between slices — and exposes *collective* costs
(all-reduce, all-gather, reduce-scatter, all-to-all, ppermute) rather
than point-to-point NCCL costs, because XLA emits collectives.  The
same interface backs the event simulator and the search.

All times in seconds, sizes in bytes.
"""
from __future__ import annotations

import dataclasses
import json
import math
from typing import Dict, Optional, Sequence, Tuple


@dataclasses.dataclass
class DeviceSpec:
    """Per-chip compute/memory capability (defaults: TPU v5p)."""

    peak_flops: float = 459e12  # bf16 FLOP/s (v5p)
    peak_flops_f32: float = 115e12
    hbm_bandwidth: float = 2765e9  # bytes/s (v5p 2.77 TB/s)
    hbm_capacity: float = 95e9  # bytes
    vmem_bytes: float = 128 * 2**20


V5E_DEVICE = DeviceSpec(
    peak_flops=197e12, peak_flops_f32=49e12, hbm_bandwidth=819e9,
    hbm_capacity=16e9,
)
V5P_DEVICE = DeviceSpec()

#: `jax.Device.device_kind`, verbatim as the chip reports it -> spec.
#: "TPU v5 lite" is what a v5e answers (read on the chip, PR 21).  An
#: accelerator missing here is an ERROR in detect_device_spec, never a
#: default: a wrong roofline silently skews every search cost and MFU.
DEVICE_SPECS: Dict[str, DeviceSpec] = {
    "TPU v5 lite": V5E_DEVICE,
}

#: what the CPU backend is costed as — the hermetic search tests need
#: SOME roofline and have always been priced on v5p peaks.  Never
#: served to an accelerator.
CPU_BACKEND_DEVICE = V5P_DEVICE


def detect_device_spec() -> DeviceSpec:
    """Spec for the LIVE backend by device_kind — the reference
    profiles the actual GPU (model.cu:38); calibrated analytic costs
    need the actual chip's roofline too.  A backend that cannot
    initialise raises out of jax.devices(); an accelerator whose kind
    is not in DEVICE_SPECS raises here."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return CPU_BACKEND_DEVICE
    spec = DEVICE_SPECS.get(dev.device_kind)
    if spec is None:
        raise ValueError(
            f"no DeviceSpec for {dev.platform} device_kind "
            f"{dev.device_kind!r} (known: {sorted(DEVICE_SPECS)}); add "
            "its published peaks to sim/machine_model.py DEVICE_SPECS "
            "— costing it as another chip would misprice every "
            "strategy")
    return spec


class MachineModel:
    """Interface consumed by the simulator/search."""

    version: int = -1

    def num_devices(self) -> int:
        raise NotImplementedError

    def device(self) -> DeviceSpec:
        raise NotImplementedError

    def p2p_time(self, size: int, src: int, dst: int) -> float:
        raise NotImplementedError

    # -- collective costs over a device group ---------------------------
    def allreduce_time(self, size: int, group: Sequence[int]) -> float:
        n = len(group)
        if n <= 1:
            return 0.0
        # ring: 2 (n-1)/n * size over the slowest link in the group
        bw, lat = self._group_link(group)
        return 2.0 * (n - 1) / n * size / bw + 2 * (n - 1) * lat

    def allgather_time(self, size: int, group: Sequence[int]) -> float:
        n = len(group)
        if n <= 1:
            return 0.0
        bw, lat = self._group_link(group)
        return (n - 1) / n * size / bw + (n - 1) * lat

    def reducescatter_time(self, size: int, group: Sequence[int]) -> float:
        return self.allgather_time(size, group)

    def ps_link(self) -> Tuple[float, float]:
        """(bandwidth, latency) for parameter-server gradient sync —
        the reference's flat 2*size/BW sync estimate
        (simulator.cc:786-813) rides one link to the server."""
        if hasattr(self, "ici_bw"):  # TpuPodModel
            return self.ici_bw, self.ici_lat
        if hasattr(self, "link_bw"):  # NetworkedMachineModel (network.py:223)
            return self.link_bw, self.link_lat
        return (  # SimpleMachineModel
            getattr(self, "intra_bw", 100e9),
            getattr(self, "intra_lat", 1e-6),
        )

    def alltoall_time(self, size: int, group: Sequence[int]) -> float:
        n = len(group)
        if n <= 1:
            return 0.0
        bw, lat = self._group_link(group)
        return (n - 1) / n * size / bw + (n - 1) * lat

    def _group_link(self, group: Sequence[int]) -> Tuple[float, float]:
        """(bandwidth, latency) of the slowest link inside the group."""
        raise NotImplementedError


class SimpleMachineModel(MachineModel):
    """Flat two-level model for parity with the reference's v0
    (machine_model.cc:58: intra-node bw, inter-node bw/num_nodes)."""

    version = 0

    def __init__(self, num_nodes: int = 1, devices_per_node: int = 8,
                 device: DeviceSpec = V5P_DEVICE,
                 intra_bw: float = 100e9, inter_bw: float = 25e9,
                 intra_lat: float = 1e-6, inter_lat: float = 10e-6):
        self._num_nodes = num_nodes
        self._per_node = devices_per_node
        self._device = device
        self.intra_bw, self.inter_bw = intra_bw, inter_bw
        self.intra_lat, self.inter_lat = intra_lat, inter_lat

    def num_devices(self) -> int:
        return self._num_nodes * self._per_node

    def device(self) -> DeviceSpec:
        return self._device

    def node_of(self, d: int) -> int:
        return d // self._per_node

    def p2p_time(self, size: int, src: int, dst: int) -> float:
        if src == dst:
            return 0.0
        if self.node_of(src) == self.node_of(dst):
            return self.intra_lat + size / self.intra_bw
        return self.inter_lat + size / (self.inter_bw / max(1, self._num_nodes))

    def _group_link(self, group: Sequence[int]) -> Tuple[float, float]:
        nodes = {self.node_of(d) for d in group}
        if len(nodes) > 1:
            return self.inter_bw, self.inter_lat
        return self.intra_bw, self.intra_lat


class TpuPodModel(MachineModel):
    """ICI torus + DCN machine model for TPU pod slices.

    topology: per-axis chip counts of the slice, e.g. (4, 4) for v5p-32
    (16 chips in a 4x4 torus), (2, 2, 1) etc.  Mesh axes of the strategy
    map onto torus axes in order — the canonical layout the real
    mesh_utils.create_device_mesh produces — so a collective over mesh
    axis i rides the per-hop ICI bandwidth of torus axis i.

    slices > 1 models multi-slice training: groups spanning slices pay
    DCN cost per host.
    """

    version = 2

    def __init__(
        self,
        topology: Tuple[int, ...] = (4, 4),
        device: DeviceSpec = V5P_DEVICE,
        ici_bw_per_link: float = 90e9,  # bytes/s each direction (v5p ~100GB/s)
        ici_latency: float = 1e-6,
        dcn_bw_per_host: float = 25e9,
        dcn_latency: float = 10e-6,
        slices: int = 1,
    ):
        self.topology = tuple(topology)
        self._device = device
        self.ici_bw = ici_bw_per_link
        self.ici_lat = ici_latency
        self.dcn_bw = dcn_bw_per_host
        self.dcn_lat = dcn_latency
        self.slices = slices

    @classmethod
    def from_file(cls, path: str) -> "TpuPodModel":
        with open(path) as f:
            d = json.load(f)
        dev = DeviceSpec(**d.get("device", {}))
        return cls(
            topology=tuple(d.get("topology", (4, 4))),
            device=dev,
            ici_bw_per_link=d.get("ici_bw_per_link", 90e9),
            ici_latency=d.get("ici_latency", 1e-6),
            dcn_bw_per_host=d.get("dcn_bw_per_host", 25e9),
            dcn_latency=d.get("dcn_latency", 10e-6),
            slices=d.get("slices", 1),
        )

    def num_devices(self) -> int:
        n = self.slices
        for t in self.topology:
            n *= t
        return n

    def device(self) -> DeviceSpec:
        return self._device

    def coords(self, d: int) -> Tuple[int, ...]:
        out = []
        for t in reversed(self.topology):
            out.append(d % t)
            d //= t
        return tuple(reversed(out))

    def p2p_time(self, size: int, src: int, dst: int) -> float:
        if src == dst:
            return 0.0
        a, b = self.coords(src % self._chips_per_slice()), self.coords(
            dst % self._chips_per_slice()
        )
        if src // self._chips_per_slice() != dst // self._chips_per_slice():
            return self.dcn_lat + size / self.dcn_bw
        hops = 0
        for ai, bi, t in zip(a, b, self.topology):
            d = abs(ai - bi)
            hops += min(d, t - d)  # torus wraparound
        return hops * self.ici_lat + size / self.ici_bw

    def _chips_per_slice(self) -> int:
        n = 1
        for t in self.topology:
            n *= t
        return n

    def _group_link(self, group: Sequence[int]) -> Tuple[float, float]:
        per_slice = self._chips_per_slice()
        slices = {d // per_slice for d in group}
        if len(slices) > 1:
            return self.dcn_bw, self.dcn_lat
        return self.ici_bw, self.ici_lat

    # -- axis-aware collective costs (preferred API) --------------------
    # `lat_scale` scales the per-hop latency term only (bandwidth bytes
    # are untouched): the DCN grad-sync bucketing amortizes a bucketed
    # leaf's launch latency over the bucket it rides in
    # (sim/simulator.py _collective).  1.0 = the unbucketed estimate.
    def axis_allreduce_time(self, size: int, axis_len: int,
                            over_dcn: bool = False,
                            lat_scale: float = 1.0) -> float:
        """Bidirectional-ring all-reduce along one torus axis: each of
        the two directions carries half the data, so the effective
        bandwidth is 2 links."""
        if axis_len <= 1:
            return 0.0
        bw = self.dcn_bw if over_dcn else 2.0 * self.ici_bw
        lat = (self.dcn_lat if over_dcn else self.ici_lat) * lat_scale
        return 2.0 * (axis_len - 1) / axis_len * size / bw + 2 * (axis_len - 1) * lat

    def axis_allgather_time(self, size: int, axis_len: int,
                            over_dcn: bool = False,
                            lat_scale: float = 1.0) -> float:
        if axis_len <= 1:
            return 0.0
        bw = self.dcn_bw if over_dcn else 2.0 * self.ici_bw
        lat = (self.dcn_lat if over_dcn else self.ici_lat) * lat_scale
        return (axis_len - 1) / axis_len * size / bw + (axis_len - 1) * lat

    def axis_alltoall_time(self, size: int, axis_len: int,
                           over_dcn: bool = False,
                           lat_scale: float = 1.0) -> float:
        if axis_len <= 1:
            return 0.0
        bw = self.dcn_bw if over_dcn else 2.0 * self.ici_bw
        lat = (self.dcn_lat if over_dcn else self.ici_lat) * lat_scale
        t_bw = (axis_len - 1) / axis_len * size / bw
        if not over_dcn:
            # on a ring/torus axis the all-to-all is bisection-bound:
            # ~axis_len/4 of the traffic crosses the cut links (scales
            # the bandwidth term only, not per-hop latency)
            t_bw *= max(1.0, axis_len / 4.0)
        return t_bw + (axis_len - 1) * lat


def make_machine_model(config, num_devices: int) -> MachineModel:
    """Build from FFConfig (--machine-model-version/-file parity).
    Device roofline matches the live chip (detect_device_spec: the CPU
    backend is costed as CPU_BACKEND_DEVICE, keeping hermetic tests
    deterministic; an unknown accelerator raises).  --slices > 1 selects the
    multi-slice hierarchy (topology/hierarchy.py SliceHierarchy: ICI
    inside each slice, DCN between) regardless of model version — the
    hierarchy is what the searches must see; 1 slice is exactly the
    flat pre-topology behavior."""
    if getattr(config, "slices", 1) > 1:
        # a degraded mesh (elastic re-search on survivors) may no
        # longer split into equal slices — or match the configured
        # per-slice topology's chip count: degrade to the flat model
        # rather than failing recovery over a cost-model nicety
        import logging

        try:
            from ..topology.hierarchy import hierarchy_from_config

            return hierarchy_from_config(config, num_devices)
        except ValueError as e:
            logging.getLogger("flexflow_tpu.topology").warning(
                "slice hierarchy unusable for %d devices (%s); falling "
                "back to the flat machine model", num_devices, e,
            )
    if config.machine_model_file:
        return TpuPodModel.from_file(config.machine_model_file)
    spec = detect_device_spec()
    if config.machine_model_version == 0:
        return SimpleMachineModel(
            num_nodes=max(1, config.num_nodes),
            devices_per_node=max(1, num_devices // max(1, config.num_nodes)),
            device=spec,
        )
    # default TPU pod: 1-D ring topology of the right size
    return TpuPodModel(topology=(num_devices,), device=spec)
