"""Batched TPU kernels: the compute path that replaces serial Z3 dispatch.

- ``batched_sat``: lockstep BCP + randomized probing over an HBM-resident
  shared clause pool — decides whole frontiers of path-feasibility
  queries per device step (see BASELINE.json north star).
- ``u256``: 8x32-bit limb arithmetic primitives for batched EVM state
  stepping (used by later rounds' lockstep interpreter).
"""

import os

_jax_configured = False


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives: the operator's
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``
    (a fixed path, so later runs of this checkout hit it)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))),
        ".jax_cache",
    )


def configure_jax() -> None:
    """One-time process-wide JAX setup, before the first jit of every
    entry point: point the persistent compilation cache at
    :func:`compile_cache_dir` (first TPU compile of a solve step costs
    seconds; cached reloads are near-instant across processes).  The
    CPU backend keeps no cache: its AOT entries are machine-feature
    specific and can SIGILL when reloaded on another host."""
    global _jax_configured
    if _jax_configured:
        return
    _jax_configured = True
    import jax

    from mythril_tpu.resilience.watchdog import compile_clock

    # compile seconds are counted per thread (and per analysis record)
    # from the first compile on
    compile_clock()
    if jax.default_backend() == "cpu":
        return
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    # every compile is cached, so a rerun of the same work adds no entry
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
