"""What the entry points share about the machine they run on: the device
identity every measured line carries, the refusal of a platform that is
not the chip (``chip_smoke.py`` and ``bench.py`` — library code and the
tests keep running on the CPU), and where JAX's persistent compile cache
lives."""

from __future__ import annotations

import os
from typing import Dict, List, Optional

# explicit argument of chip_smoke.py and bench.py: run tiny sizes on
# whatever platform JAX finds and report no result.  Never a default and
# never an environment variable — a measurement must not be able to fall
# back to the CPU by accident.
REHEARSAL_FLAG = "--cpu-rehearsal"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def device_record() -> Dict:
    """The device as JAX reports it: platform, device_kind and count."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(what: str, rehearsal: bool = False) -> Dict:
    """:func:`device_record`, refusing (SystemExit) any platform other
    than ``tpu`` unless this is an explicit rehearsal — which is pinned
    to the CPU, so that it never takes a chip.  Call before anything
    asks JAX for a device."""
    if rehearsal:
        import jax

        jax.config.update("jax_platforms", "cpu")
    dev = device_record()
    if dev["platform"] != "tpu" and not rehearsal:
        raise SystemExit(
            f"{what}: JAX found platform {dev['platform']!r} "
            f"({dev['kind']}, {dev['count']} device(s)), not a TPU — "
            f"refusing to run: a number from this platform is not a "
            f"device measurement.  {REHEARSAL_FLAG} is for debugging at "
            f"tiny sizes and prints nothing that reads as a result.")
    return dev


def max_memory_stat(devices, key: str) -> Optional[int]:
    """The largest ``memory_stats()[key]`` over ``devices`` — the fullest
    chip, since a placed plan loads devices unevenly and device 0 alone
    is not the machine; None where the backend reports no such stat (the
    CPU client reports none at all)."""
    return max((s[key] for s in (d.memory_stats() or {} for d in devices)
                if key in s), default=None)


def device_account(devices, *trees) -> List[Dict]:
    """What each device holds: the bytes of ``trees``' array shards
    resident on it (``addressable_shards``) and, where the backend
    reports them (the CPU client does not), the runtime's in-use and peak
    bytes.  One row per device of ``devices`` — looking at device 0 alone
    cannot show that a placed plan loads every chip."""
    import jax

    held = {d.id: 0 for d in devices}
    for leaf in jax.tree.leaves(trees):
        for sh in getattr(leaf, "addressable_shards", ()):
            if sh.device.id in held:
                held[sh.device.id] += sh.data.nbytes
    rows = []
    for d in devices:
        stats = d.memory_stats() or {}
        rows.append({"id": d.id, "platform": d.platform,
                     "state_bytes": held[d.id],
                     "bytes_in_use": stats.get("bytes_in_use"),
                     "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return rows


def compile_cache_dir() -> Optional[str]:
    """The directory this program sets for JAX's persistent compile
    cache: None when ``JAX_COMPILATION_CACHE_DIR`` places it from outside
    (JAX reads that itself), otherwise ``<checkout>/.jax_cache``.  Always
    a fixed path: a directory that moves (tempfile, pid, time) is never
    found again by the next run."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX at the compile cache; call before the first compile of
    the process (JAX decides once whether the cache is in use).  Returns
    the directory in effect."""
    path = compile_cache_dir()
    if path is None:
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    return path
