"""Pallas TPU kernels for the serving hot paths.

Each kernel follows the <name>.py (pl.pallas_call + BlockSpec) / ops.py
(jit'd wrappers) / ref.py (pure-jnp oracle) convention; tests sweep
shapes/dtypes and assert_allclose against the oracles in interpret mode.

The package re-exports nothing: a function bound under a submodule's
name (``span_attention``) would shadow that submodule for
``from repro.kernels import span_attention``.  Import from the
submodules.
"""
