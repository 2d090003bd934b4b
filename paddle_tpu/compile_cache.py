"""Where XLA's persistent compilation cache lives — the one rule.

Every entry point (`import paddle_tpu`, `python -m
paddle_tpu.serving.replica`, a `distributed/launch.py` worker,
`benchmarks.run`, chip_smoke.py) imports this package before it compiles
anything, so the rule is applied here, once, at package import:

- `JAX_COMPILATION_CACHE_DIR` set by the caller: that directory, and
  nothing in code names another;
- otherwise `<checkout>/.jax_cache`, a fixed path (the path is part of
  the cache key, so a directory that moves never hits).

A serving replica compiles one program per bucket and a Mosaic kernel
can take minutes; with one shared directory those are paid once per
program per tree, not once per process.

A process pinned to the CPU (JAX_PLATFORMS=cpu: the test suite, the
CPU-pinned bench rows, worker children) keeps the directory but does
not use it: its programs compile in seconds, and XLA:CPU logs an
error-level machine-feature line for every cache entry it loads.
"""

import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# env name -> value as jax's config takes it (the env carries str(value))
_DEFAULTS = {
    "JAX_COMPILATION_CACHE_DIR": os.path.join(_CHECKOUT, ".jax_cache"),
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": 1.0,
    "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": -1,
}


def cache_dir(environ=None):
    """The directory in force under the rule above."""
    env = os.environ if environ is None else environ
    return (env.get("JAX_COMPILATION_CACHE_DIR")
            or _DEFAULTS["JAX_COMPILATION_CACHE_DIR"])


def install(environ=None):
    """Apply the rule to `environ` (default: this process).  jax reads
    these variables when it is imported; a process that imported jax
    first gets the values that were missing set on its live config."""
    env = os.environ if environ is None else environ
    live = sys.modules.get("jax") if env is os.environ else None
    missing = {k: v for k, v in _DEFAULTS.items() if not env.get(k)}
    platforms = (live.config.jax_platforms if live is not None
                 else env.get("JAX_PLATFORMS")) or ""
    if platforms.split(",")[0].strip().lower() == "cpu":
        missing["JAX_ENABLE_COMPILATION_CACHE"] = False
    for k, v in missing.items():
        env[k] = str(v)
        if live is not None:
            live.config.update(k.lower(), v)
    return env
