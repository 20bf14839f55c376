"""Platform-dependent primitive selection.

The suffix-array hot path is sort-bound, and the best sort primitive
differs per platform: XLA's `lax.sort` is a single-threaded comparison sort
on CPU (~50× slower than the host radix/introsort at n=200k on a CPU host)
but is the native fast path on TPU/GPU, and the Mosaic Pallas kernels in
`repro.kernels` only compile on TPU (elsewhere they run in the slow
`interpret=True` mode). `default_sort_impl()` / `pallas_available()` encode
that decision tree once so `repro.core.dcv_jax` and the `repro.api`
registry never hard-code a platform assumption (see docs/architecture.md).
A backend that fails to start raises; nothing here falls back to the CPU.
"""
from __future__ import annotations

import functools

import jax


@functools.lru_cache(maxsize=None)
def backend_platform() -> str:
    """The default jax backend platform: "cpu", "tpu", or "gpu"."""
    return jax.default_backend()


def pallas_available() -> bool:
    """True when the Pallas kernels in `repro.kernels` can run *compiled*
    (Mosaic on TPU). Elsewhere they only run under `interpret=True`, which
    executes kernel bodies in Python and is strictly slower than the lax /
    host fallbacks — callers should treat that as "unavailable" for
    performance selection (it stays usable for correctness testing).
    """
    return backend_platform() == "tpu"


def default_sort_impl() -> str:
    """Resolve `sort_impl="auto"` for the current platform.

    ==========  ==========================================================
    "radix"     CPU — packed-key host sorts (numpy introsort / LSD radix
                passes); XLA's CPU `lax.sort` is a single-threaded
                comparison sort and loses by ~50× at n=200k.
    "lax"       TPU/GPU — XLA's native variadic `lax.sort`, one fused
                multi-key sort per round, no host round-trips.
    ==========  ==========================================================

    The Pallas row-sort path is *not* auto-selected yet even on TPU (the
    fused `lax.sort` is at least as good for these payload widths); request
    it explicitly with ``sort_impl="pallas"``.
    """
    return "lax" if backend_platform() in ("tpu", "gpu") else "radix"
