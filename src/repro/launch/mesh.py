"""Production mesh factory and the launch-side device helpers.

Mesh construction (and all jax mesh/shard_map API use) lives in the
runtime layer; this module keeps the launch-facing names, the per-chip
peak table the roofline analysis consumes, and the two checks every entry
point that is meant for a chip makes: that it landed on the platform it
asked for, and where JAX keeps its compile cache.  FUNCTIONS, not
module-level device state — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first init).

Single pod : (data=16, model=16)            = 256 chips (one v5e pod)
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips

`model` maps to intra-pod ICI neighbors (TP/EP/gossip ring), `data` to the
remaining intra-pod dimension (DP/FSDP), `pod` to the cross-pod DCI links
(pure DP — only gradient all-reduce crosses pods).
"""

from __future__ import annotations

import os
import pathlib

from repro.runtime import dist

# <repo>/.jax_cache: a fixed path, so every run of this checkout finds the
# programs the previous one compiled (the path is part of the cache key).
REPO_COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def make_production_mesh(*, multi_pod: bool = False):
    return dist.production_mesh(multi_pod=multi_pod)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / elastic rescale)."""
    return dist.make_mesh(tuple(shape), tuple(axes))


# Per-chip peaks, keyed by `jax.Device.device_kind`.  Source: Google Cloud
# documentation, "TPU v5e" (system architecture: 197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect over 4 links).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,  # FLOP/s
        "hbm_bw": 819e9,  # B/s
        "ici_bw": 50e9,  # B/s per link
        "hbm_bytes": 16e9,
    },
}

# The chip the dry-run and the roofline analysis model (a v5e pod).
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """The peak table row of one chip kind; an unknown kind is an error
    (a roofline against some other chip's peaks is a wrong number)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak table entry for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add the chip with its published source)"
        ) from None


def require_platform(platform: str) -> None:
    """Fail loudly unless JAX's default devices are on `platform`: a run
    meant for the chip must never carry on on the CPU it fell back to."""
    import jax

    got = jax.devices()[0].platform
    if got != platform:
        raise SystemExit(
            f"this run needs platform {platform!r} but JAX found {got!r} "
            f"devices ({jax.devices()[0].device_kind}); refusing to fall back"
        )


def use_repo_compile_cache() -> None:
    """Keep JAX's persistent compile cache in `<repo>/.jax_cache` unless
    JAX_COMPILATION_CACHE_DIR names one (JAX honours that itself, and then
    this sets nothing).  Call at run time, from an entry point."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_COMPILE_CACHE))
