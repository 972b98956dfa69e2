"""Platform resolution: one home for the set_platform / XLA-flags /
host-device-count idiom, and the single source of truth for whether
Pallas kernels run compiled or in interpret mode.

Before this module, ``interpret=True`` was a hard default on every
kernel entry point, which meant the "kernel" backend was silently an
interpret-mode emulation even on accelerator hosts. Now every entry
point defaults to ``interpret=None`` and resolves it here:

    interpret = None   -> interpret mode iff no accelerator is attached
    interpret = bool   -> honored as given (tests pin interpret=True to
                          run kernel paths on CPU CI)

On a TPU an explicit ``interpret=True`` is an error: the chip runs
compiled kernels only, so no kernel path there can silently fall back to
the interpreter.

The module also keys the roofline hardware presets
(``repro.roofline.model.HW_PRESETS``) by the ``device_kind`` JAX reports,
and raises for a device it has no preset for, so a peak fraction is only
ever computed against the hardware that ran the bench.

``enable_compile_cache`` places JAX's persistent compilation cache for
entry-point scripts; importing the library never touches it.

Environment mutation (``set_platform`` / ``set_host_device_count``)
must happen before JAX initializes its backends — call these at process
start (the compression bench does it via a subprocess env; see
``xla_host_device_flags``).
"""
from __future__ import annotations

import os
import warnings
from typing import Optional

__all__ = [
    "set_platform",
    "set_host_device_count",
    "xla_host_device_flags",
    "default_backend",
    "has_accelerator",
    "resolve_interpret",
    "donate_state_buffers",
    "checkout_root",
    "enable_compile_cache",
    "device_kind",
    "hw_config",
    "vmem_budget_bytes",
    "lanes_for",
    "warn_explicit_interpret",
]


def set_platform(platform: str = "cpu") -> None:
    """Force JAX onto ``platform`` ('cpu' | 'gpu' | 'tpu').

    Must run before any JAX computation. On GPU the usual allocator
    flags are appended to XLA_FLAGS so a forced-GPU process does not
    grab the whole card up front.
    """
    import jax

    if platform == "gpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_gpu_autotune_level" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_gpu_autotune_level=2"
            ).strip()
        os.environ.setdefault("XLA_PYTHON_CLIENT_PREALLOCATE", "false")
    jax.config.update("jax_platform_name", platform)


def xla_host_device_flags(n: int) -> str:
    """The XLA_FLAGS value that emulates ``n`` host (CPU) devices.

    Returned as a string (not applied) so callers can build a subprocess
    env — the flag only takes effect before XLA backend init, so the
    running process usually cannot apply it to itself.
    """
    return f"--xla_force_host_platform_device_count={n}"


def set_host_device_count(n: int) -> None:
    """Emulate ``n`` CPU devices in *this* process (pre-JAX-init only)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " + xla_host_device_flags(n)).strip()


def default_backend() -> str:
    """The effective JAX backend: 'cpu', 'gpu', or 'tpu'."""
    import jax

    return jax.default_backend()


def has_accelerator() -> bool:
    return default_backend() != "cpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve a tri-state ``interpret`` argument to a concrete bool.

    ``None`` (the default on every kernel entry point) means "compiled
    kernel when an accelerator is attached, interpret emulation
    otherwise". An explicit bool is honored unchanged off the TPU so CPU
    CI can pin kernel paths with ``interpret=True``; on a TPU that pin
    raises instead of running the interpreter on the chip's host.
    """
    if interpret is None:
        return not has_accelerator()
    if interpret and default_backend() == "tpu":
        raise ValueError(
            "interpret=True on a TPU: Pallas kernels run compiled on the "
            "chip; leave interpret=None")
    return bool(interpret)


def donate_state_buffers() -> bool:
    """Whether jit should donate sketch-state operands.

    Donation lets XLA reuse the incoming bank buffer for the output —
    the right call on accelerators where the bank is large and HBM
    copies cost real bandwidth. On CPU it stays off by policy, not
    because the runtime ignores it: JAX's CPU backend honors donation
    (a donated array reads back ``is_deleted()``). Tests that must
    exercise the donated path without a chip force this to True
    (tests/test_sketch_service.py), so a use-after-donate shows up on
    the CPU first.
    """
    return has_accelerator()


def checkout_root() -> str:
    """The repository checkout this package runs from (``src/..``)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Called by entry-point scripts (``chip_smoke.py``,
    ``benchmarks/run.py``), never on import. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets no other directory. Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``: the path is part of the cache key, so a
    temporary or per-process directory would never hit.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(checkout_root(), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_kind() -> str:
    """``device_kind`` of the first JAX device (e.g. 'TPU v5 lite')."""
    import jax

    return jax.devices()[0].device_kind


def hw_config(kind: Optional[str] = None):
    """The roofline HWConfig for ``kind``, or for the attached device.

    Presets are keyed by the ``device_kind`` JAX reports. A kind with no
    preset — the CPU among them — raises: a peak fraction against some
    other device's peaks is a wrong number, not a default.
    """
    from repro.roofline.model import hw_for

    return hw_for(device_kind() if kind is None else kind)


def vmem_budget_bytes(platform: Optional[str] = None) -> int:
    """Usable fast-memory budget per core for kernel tile sizing.

    TPU VMEM is ~16 MiB/core; we budget half of it so the grid pipeline
    can double-buffer input tiles (two slots resident at once). GPU SMEM
    is far smaller but Pallas/Triton tiles spill to L2, so we allow the
    same logical budget; CPU interpret mode has no real constraint but
    uses the TPU budget so tile shapes match what would run on hardware.
    """
    del platform  # one budget keeps tile geometry platform-stable
    return (16 * 1024 * 1024) // 2


def lanes_for(platform: Optional[str] = None) -> int:
    """Minor-axis alignment for counter tiles (TPU lane width)."""
    del platform  # 128 lanes on TPU; kept for GPU/CPU so layouts agree
    from repro.sketch.state import LANES

    return int(LANES)


def warn_explicit_interpret(where: str) -> None:
    """DeprecationWarning for sketch-API callers passing interpret=True.

    The sketch layer resolves interpret from the platform now; an
    explicit True silently pins emulation mode even on accelerator
    hosts. Kernel-level ops keep accepting it without warning (tests
    pin interpret=True there deliberately).
    """
    warnings.warn(
        f"{where}: passing interpret=True explicitly is deprecated; "
        "leave interpret=None and let repro.platform resolve it "
        "(interpret mode is used automatically when no accelerator is "
        "attached)",
        DeprecationWarning,
        stacklevel=3,
    )
