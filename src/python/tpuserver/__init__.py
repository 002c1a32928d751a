"""tpuserver — an in-process, TPU-native inference serving runtime.

Plays the role the reference's ``triton_c_api`` backend plays (reference
client_backend/triton_c_api/triton_loader.h:85-115: dlopen'd in-process
``libtritonserver.so``): a full KServe-v2 server the client stack can talk to
— over real HTTP and gRPC frontends or via direct in-process calls — without
any external deployment.  Models execute as jitted JAX computations on
whatever ``jax.devices()`` provides (TPU in production, CPU in tests), so the
same runtime serves both the test suite and the TPU benchmarks.

The platform comes from the environment alone (``JAX_PLATFORMS``): nothing
in this package sets or defaults it.  Entry points that report device
numbers call :func:`require_tpu` and fail off-chip instead of falling back.
"""

import os

from tpuserver.core import InferenceServer, JaxModel, Model, TensorSpec

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..")
)


def enable_compile_cache():
    """Turn on jax's persistent compilation cache and return its
    directory — the ONE place this repo places it.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads the directory from
    the environment and none is set in code; otherwise it is
    ``<repo>/.jax_cache`` (git-ignored).  The path is part of the cache
    key, so it must not move between runs.  Call before the first
    compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(_REPO_ROOT, ".jax_cache"),
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return jax.config.jax_compilation_cache_dir


def require_tpu():
    """The platform check of every chip entry point (``chip_smoke.py``,
    the bench scripts that report device metrics): return jax's first
    device, or raise unless it is a TPU whose ``device_kind`` the peaks
    table (``ops.perf.CHIP_SPECS``) knows.  A process that could not
    take the chip must fail here, not answer from the CPU.  Also states
    the Pallas kernel mode as Mosaic, so nothing downstream can pick
    the interpreter."""
    import jax

    from tpuserver.ops import flash, perf

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise RuntimeError(
            "this entry point needs a TPU but jax found platform "
            "'{}' ({} x {}); JAX_PLATFORMS={!r}.  Another process may "
            "hold the chip, or the environment pins the CPU".format(
                device.platform, len(jax.devices()), device.device_kind,
                os.environ.get("JAX_PLATFORMS"))
        )
    perf.chip_spec(device)  # raises for a kind the peaks table lacks
    flash.set_kernel_mode(interpret=False)
    return device


__all__ = [
    "InferenceServer", "JaxModel", "Model", "TensorSpec",
    "enable_compile_cache", "require_tpu",
]
