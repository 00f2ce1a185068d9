"""Test-session setup: no persistent compilation cache.

The train/serve CLIs turn JAX's persistent cache on
(:mod:`repro.launch.compile_cache`); tests that call them in-process would
otherwise write compiled CPU programs into the checkout, from several
xdist workers at once.
"""

import jax

jax.config.update("jax_enable_compilation_cache", False)
