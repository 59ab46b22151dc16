"""The benchmark's own tests run on the CPU at tiny sizes: JAX on the CPU
backend, the persistent compile cache off (nothing left in the checkout),
and the checkout root importable (``benchmark.*`` and the program)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
