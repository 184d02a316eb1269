"""Hardware detection for TPU hosts.

Capability parity: reference ``src/parallax/server/server_info.py:28-229``
(Apple/NVIDIA device DBs + detect_node_hardware). Here the node is a TPU
host: we report per-chip peak bf16 TFLOPS, HBM capacity/bandwidth and the
local chip count so the global scheduler's roofline model can place layers.
"""

from __future__ import annotations

import dataclasses

# Peak specs per chip: (bf16 TFLOPS, HBM GiB, HBM GB/s, ICI GB/s per link).
TPU_CHIP_DB: dict[str, tuple[float, float, float, float]] = {
    "v4": (275.0, 32.0, 1228.0, 100.0),
    "v5e": (197.0, 16.0, 819.0, 186.0),
    "v5p": (459.0, 95.0, 2765.0, 200.0),
    "v6e": (918.0, 32.0, 1640.0, 227.0),
    "cpu": (1.0, 8.0, 50.0, 10.0),       # the CPU platform (tests)
}


@dataclasses.dataclass
class HardwareInfo:
    """Per-node hardware summary shipped to the global scheduler on join."""

    device_kind: str          # e.g. "v5e"
    num_chips: int            # chips visible to this host (the TP degree)
    tflops_bf16: float        # per chip
    hbm_gib: float            # per chip
    hbm_gbps: float           # per chip
    ici_gbps: float

    @property
    def total_tflops(self) -> float:
        return self.tflops_bf16 * self.num_chips

    @property
    def total_hbm_bytes(self) -> int:
        return int(self.hbm_gib * self.num_chips * (1 << 30))

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "HardwareInfo":
        return cls(**d)


def _device_kind_key(platform: str, kind: str) -> str:
    """Map a PJRT device (platform, device_kind) to our spec-DB key.

    JAX reports e.g. "TPU v4", "TPU v5 lite"/"TPU v5e", "TPU v5p"/"TPU v5",
    "TPU v6 lite"/"TPU v6e". The ``cpu`` row is for the CPU platform
    only; an accelerator the table does not know is an error, never a
    guess — invented peaks would size real placements.
    """
    if platform == "cpu":
        return "cpu"
    kind_l = kind.lower()
    if platform == "tpu":
        if "v6" in kind_l:
            return "v6e"
        if "v5" in kind_l:
            return "v5e" if ("lite" in kind_l or "v5e" in kind_l) else "v5p"
        if "v4" in kind_l:
            return "v4"
    raise ValueError(
        f"unknown accelerator: platform {platform!r}, device_kind "
        f"{kind!r} (add its peaks to utils/hw.py TPU_CHIP_DB)"
    )


def detect_hardware() -> HardwareInfo:
    """Probe jax for the local device topology."""
    import jax

    devices = jax.local_devices()
    kind = _device_kind_key(devices[0].platform, devices[0].device_kind)
    tflops, hbm, bw, ici = TPU_CHIP_DB[kind]
    # Live capacity where the runtime reports it (the CPU backend
    # reports no memory stats).
    stats = devices[0].memory_stats()
    if stats and "bytes_limit" in stats:
        hbm = stats["bytes_limit"] / (1 << 30)
    return HardwareInfo(
        device_kind=kind,
        num_chips=len(devices),
        tflops_bf16=tflops,
        hbm_gib=hbm,
        hbm_gbps=bw,
        ici_gbps=ici,
    )


def device_report() -> dict:
    """What JAX says this process runs on, for status payloads: the
    first device's platform and kind, the device count, and each local
    device's live memory counters (None where the backend has none)."""
    import jax

    devices = jax.devices()
    per_device = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        per_device.append({
            "id": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        })
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "devices": per_device,
    }


def host_available_memory_bytes() -> int:
    """Host DRAM available for the KV offload tier (0 when unknown).

    Linux ``MemAvailable`` (kernel's reclaimable estimate) rather than
    MemFree: page cache the kernel would drop under pressure should
    count toward the tier budget.
    """
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


def default_host_cache_bytes(
    fraction: float = 0.5, override: int | None = None
) -> int:
    """Host-KV-tier budget: the operator's explicit value when given,
    otherwise half of available DRAM on accelerator backends (so the
    tier never drives the host into swap). 0 (tier off) on CPU or when
    availability cannot be read — CPU test runs configure the budget
    explicitly. The single policy point for serve and swarm workers."""
    if override is not None:
        return override
    import jax

    if jax.default_backend() == "cpu":
        return 0
    return int(host_available_memory_bytes() * fraction)


def device_free_memory_bytes(fraction: float = 0.9) -> int:
    """Usable HBM bytes on device 0 for KV-cache budgeting.

    Reference counterpart: ``cache_manager._calculate_cache_allocation``
    reading device free memory (src/parallax/server/cache_manager.py:354-420).
    An accelerator must report its own memory: only the CPU platform,
    which has no memory stats, is budgeted from the table.
    """
    import jax

    dev = jax.local_devices()[0]
    stats = dev.memory_stats()
    if stats and stats.get("bytes_limit"):
        used = stats.get("bytes_in_use", 0)
        return int((stats["bytes_limit"] - used) * fraction)
    if dev.platform != "cpu":
        raise RuntimeError(
            f"{dev.platform} device reports no memory limit "
            f"(memory_stats() = {stats!r}); refusing to size the KV "
            "pool from a table"
        )
    return int(TPU_CHIP_DB["cpu"][1] * (1 << 30) * fraction)
