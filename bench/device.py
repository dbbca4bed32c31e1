"""The chip under test: the accelerator guard and the table of peaks.

Every number the benchmark prints names the device it ran on. A run that
finds no accelerator, or fewer chips than its cell asks for, exits non-zero
and prints no result. A device kind that is not in ``PEAKS`` is an error,
never a default.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" is the TPU v5e: Google Cloud documentation, "TPU v5e"
# (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def accelerator(chips: int):
    """The first ``chips`` TPU devices, or exit non-zero."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no accelerator found (JAX platform "
                         f"{devs[0].platform!r}); nothing to measure")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in the peaks "
                       f"table (known: {sorted(PEAKS)})") from None


def describe(devs) -> dict:
    """The result's ``device`` object, with the peak of the fullest chip."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def use_checkout_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment says, so that only a cell's first run
    in a checkout compiles and two checkouts share nothing. The variable is
    set too, since the program's own ``use_compile_cache`` reads it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return str(CACHE_DIR)


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
