"""The benchmark's own tests run on the CPU, from the checkout's root:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
