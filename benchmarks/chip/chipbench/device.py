"""The device a run uses. There is no fallback: a run that finds no TPU,
a TPU missing from the peaks table, or fewer chips than its cell asks for
stops before it measures anything."""
from __future__ import annotations

import sys


class NoChip(RuntimeError):
    pass


def require(chips: int, peaks: dict) -> dict:
    """The device record of the result line, or NoChip."""
    import jax
    devs = jax.devices()
    d = devs[0]
    found = (f"platform {d.platform!r}, device_kind {d.device_kind!r}, "
             f"{len(devs)} device(s)")
    if d.platform != "tpu":
        raise NoChip(f"this benchmark runs on a TPU only; JAX found {found}")
    if d.device_kind not in peaks:
        raise NoChip(f"device_kind {d.device_kind!r} is not in the peaks "
                     f"table ({sorted(peaks)}); JAX found {found}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s); JAX found {found}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def prepare(chips: int, peaks: dict, root, need_chip: bool = True) -> dict:
    """What every entry point does first: the device record (after the
    checks of ``require``), the program's sources on the path, and JAX's
    persistent compile cache turned on by the program's own
    ``configure_compile_cache`` (``JAX_COMPILATION_CACHE_DIR`` when set,
    else the checkout's fixed ``.jax_cache``). ``need_chip=False`` skips
    the checks and the cache: the tests drive the harness on the CPU."""
    sys.path.insert(0, str(root / "src"))
    if not need_chip:
        import jax
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}
    dev = require(chips, peaks)
    from repro.core.executable_cache import configure_compile_cache
    configure_compile_cache()
    return dev
